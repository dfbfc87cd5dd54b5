"""Sampling for the one compiled decode step.

Greedy argmax was the only emission rule through PR 19
(docs/DIVERGENCES.md called it out). This module adds temperature /
top-p / seeded-PRNG sampling WITHOUT widening the retrace surface:
every per-request sampling parameter rides the compiled step as a
fixed-shape array argument —

  * ``temps``  (slots,)   float32 — 0.0 selects the greedy branch
  * ``top_ps`` (slots,)   float32 — nucleus mass, (0, 1]
  * ``keys``   (slots, 2) uint32  — raw threefry PRNG keys
  * ``masks``  (slots, V) float32 — optional grammar/JSON logit mask
    (additive; 0.0 = allowed, -inf/-1e9 = forbidden), compiled in
    only when the program opts in

so switching a slot between greedy and sampled traffic — or changing
temperature mid-stream — is a plain array-value change, never a
retrace.

**The sampler costs what the batch asks.** The compiled program
decides from the ``temps`` values it is handed: the greedy argmax is
always taken, and everything else (softmax, the Gumbel draw, the sort
over the vocabulary) sits in one branch of a ``lax.cond`` on
``any(temps > 0)``, so a step in which no row samples runs the argmax
alone. The branch that samples makes one stable sort by descending
probability that carries the vocabulary ids and the perturbed scores
``logp + gumbel`` along, takes the running sum and the top-p cut in
that order, and picks the best kept score there — the smallest
vocabulary id among equal maxima, as an argmax in vocabulary order
would — so nothing is gathered into the sorted order or scattered back
out of it. The engine books the steps that took that branch as
``sampled_steps`` in ``decode.counts``.

Three contracts the tests pin down:

**Greedy stays byte-identical.** The emitted token is
``where(temp > 0, sampled, argmax(logits + mask))``; with ``temp == 0``
and a zero mask the additive identity keeps the argmax input bitwise
equal to the pre-sampling program, so PR-6..19 token streams are
unchanged, not merely "statistically the same".

**Sampling is a pure function of (seed, position, logits).** The host
derives each row's key as ``key_for(seed, absolute_position)``
(blake2b, not a stateful counter), where the position is the index of
the logits row: ``len(prompt) - 1`` at prefill, ``positions[slot]``
at a step, ``positions[slot] + c`` for verify chunk ``c``. A migrated
or disagg-handed-off continuation therefore reproduces the exact
stream of the uninterrupted engine with zero extra state in the
seqstate payload beyond (seed, pos) it already carries.

**Speculation couples through shared keys.** The draft proposes with
the SAME per-position keys on its own logits; the verify program
samples the target's logits with those keys. Every emitted token is a
target-distribution draw (the verify row IS the plain-path row, same
key, same logits), so target marginals are exact — the
rejection-sampling residual is implicit: when the coupled draft draw
disagrees, the emitted "correction" token already came from the
target's own sampler. Acceptance rate r = P(draft draw == target
draw), and the 1 + k*r speculative win carries over to sampled
traffic with the greedy longest-prefix acceptance walk unchanged.
"""
from __future__ import annotations

import hashlib
import struct

import numpy as onp

__all__ = ['key_for', 'keys_for', 'sample_tokens', 'neutral_args']


def key_for(seed, pos):
    """Derive the raw (2,)-uint32 PRNG key for the logits row at
    absolute sequence position ``pos`` under stream ``seed``.

    blake2b keyed on the (seed, position) pair: independent across
    positions, reproducible across hosts — migration / disagg
    continuations land on the same keys by construction.
    """
    digest = hashlib.blake2b(b'%d|%d' % (int(seed), int(pos)),
                             digest_size=8).digest()
    hi, lo = struct.unpack('>II', digest)
    return onp.array([hi, lo], dtype=onp.uint32)


def keys_for(seed, positions):
    """Stack :func:`key_for` over ``positions`` -> (n, 2) uint32."""
    return onp.stack([key_for(seed, p) for p in positions])


def neutral_args(n):
    """(temps, top_ps, keys) selecting the greedy branch for ``n``
    rows — the defaults a sampling-capable program runs with when the
    caller passes nothing."""
    return (onp.zeros((n,), 'float32'),
            onp.ones((n,), 'float32'),
            onp.zeros((n, 2), 'uint32'))


def sample_tokens(logits, temps, top_ps, keys, masks=None):
    """Emit one token per row from ``logits`` (n, V) — traced inside
    the compiled step (also runs eagerly for the CPU fallback and the
    uncompiled test reference).

    Gumbel-max over the top-p-truncated, temperature-scaled
    distribution: deterministic in (key, logits) and exactly the
    renormalized nucleus distribution in law, with no host round-trip.
    Rows with ``temps == 0`` take the greedy branch byte-for-byte, and
    a batch in which no row samples runs the greedy argmax alone (see
    the module docstring, "The sampler costs what the batch asks").
    """
    import jax
    import jax.numpy as jnp
    with jax.named_scope('sampling'):
        logits = jnp.asarray(logits)
        if masks is not None:
            # additive grammar/JSON mask: 0.0 is the bitwise identity, so
            # an all-zero mask leaves even the greedy branch unchanged
            logits = logits + masks
        greedy = jnp.argmax(logits, axis=-1).astype('int32')
        temps = jnp.asarray(temps, 'float32')
        return jax.lax.cond(
            jnp.any(temps > 0), _nucleus_tokens,
            lambda logits, greedy, *_: greedy,
            logits, greedy, temps, jnp.asarray(top_ps, 'float32'),
            jnp.asarray(keys, 'uint32'))


def _nucleus_tokens(logits, greedy, temps, top_ps, keys):
    """The sampling branch of :func:`sample_tokens`: every row's
    nucleus draw, ``greedy`` kept for the rows with ``temps == 0``."""
    import jax
    import jax.numpy as jnp
    vocab = logits.shape[-1]
    safe_t = jnp.where(temps > 0, temps, 1.0)
    logp = jax.nn.log_softmax(logits / safe_t[:, None], axis=-1)
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (vocab,)))(keys)
    # the ids and the perturbed scores ride the sort: no gather into
    # the sorted order, no scatter back out of it
    neg_p, order, z = jax.lax.sort(
        (-jnp.exp(logp), jax.lax.broadcasted_iota('int32', logits.shape, 1),
         logp + gumbel), dimension=1, is_stable=True, num_keys=1)
    sorted_p = -neg_p
    # nucleus: keep the smallest prefix of the descending-prob order
    # whose mass reaches top_p. (csum - p) < top_p keeps the first
    # token unconditionally (0 < top_p), so the filter can never
    # empty a row.
    csum = jnp.cumsum(sorted_p, axis=-1)
    keep = (csum - sorted_p) < top_ps[:, None]
    z = jnp.where(keep, z, -jnp.inf)
    # argmax in sorted space; among equal maxima the smallest
    # vocabulary id, as an argmax in vocabulary order would take
    best = jnp.max(z, axis=-1, keepdims=True)
    sampled = jnp.min(jnp.where(z == best, order, vocab - 1), axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype('int32')
