"""What the decoder families with held experts share (``cohere2``,
``granite``): the expert layer of a chip that holds a share of the
experts, and causal grouped-head attention over a whole sequence and
over gathered rows.

**The expert layer** (:class:`HeldExperts`). The caller routes: it
hands over each row's ``top_k`` expert ids and their gate weights,
normalised however its family normalises them over ALL selected
experts, held here or not. The layer adds only what the held experts
give; nothing stands in for the absent chips. Static shapes, no
capacity, nothing dropped. A prefill sorts its assignments by expert
and takes a block of rows of every held expert to a pass
(:meth:`HeldExperts.block`: what an even router sends one expert and
four standard deviations of it), one batched product a pass, in a loop
of as many passes as the fullest expert needs: a pass reads each held
expert's weights once, and the work follows the fullest expert's
count, not experts x tokens. The decode step multiplies every slot
through every held expert and weights the unselected ones by zero: at
a handful of tokens an expert the step reads each held expert's
weights once either way, and the dense product needs no sort, no
gather and no loop.

Matrix products take ``dtype`` operands and accumulate in float32.
"""
from __future__ import annotations

import numpy as onp

__all__ = ['HeldExperts', 'attend_blocks', 'attend_rows', 'mm', 'softmax']


def mm(spec, a, b, dtype):
    """Matrix product with ``dtype`` operands, float32 result."""
    import jax.numpy as jnp
    return jnp.einsum(spec, a.astype(dtype), b,
                      preferred_element_type='float32')


def softmax(scores):
    import jax.numpy as jnp
    e = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def gated_ffn(spec_in, spec_out, n, w1, w3, w2, dtype):
    """``W2(silu(W1 n) * W3 n)`` under the given contractions."""
    import jax
    h = jax.nn.silu(mm(spec_in, n, w1, dtype)) * mm(spec_in, n, w3, dtype)
    return mm(spec_out, h, w2, dtype)


class HeldExperts:
    """The routed experts one chip holds: ``held`` (distinct ids below
    ``experts``, the router's width), ``top_k`` selected a token,
    outputs ``hidden`` wide, weights ``w1`` / ``w3`` (held, H, F) and
    ``w2`` (held, F, H) in ``dtype``."""

    def __init__(self, experts, held, top_k, hidden, dtype):
        self.experts, self.top_k = int(experts), int(top_k)
        self.held = [int(e) for e in held]
        self.hidden, self.dtype = int(hidden), str(dtype)
        if not self.held or len(set(self.held)) != len(self.held) \
                or not all(0 <= e < self.experts for e in self.held):
            raise ValueError('held_experts must be distinct ids below '
                             '%d, got %r' % (self.experts, self.held))
        if self.top_k > self.experts:
            raise ValueError('top_k %d > experts %d'
                             % (self.top_k, self.experts))
        # expert id -> index among the held ones, -1 where absent
        local = onp.full(self.experts, -1, 'int32')
        local[self.held] = onp.arange(len(self.held), dtype='int32')
        self.local_of = local

    def _ffn(self, spec_in, spec_out, n, w1, w3, w2):
        return gated_ffn(spec_in, spec_out, n, w1, w3, w2, self.dtype)

    def dense(self, n, w, top_i, live, w1, w3, w2):
        """The decode step's expert layer: every row of ``n`` (T, H)
        through every held expert, weighted by its gate weight ``w``
        (T, K) where ``top_i`` (T, K) selected it, or by zero. ``live``
        (T,) marks the rows that are sequences. Returns (routed (T, H),
        per-expert counts over live rows (held,))."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope('router'):
            hit = top_i[:, :, None] == jnp.asarray(self.held)[None, None]
            wh = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
            counts = jnp.sum(hit & live[:, None, None], axis=(0, 1))
        with jax.named_scope('experts'):
            y = self._ffn('th,ehf->etf', 'etf,efh->eth', n, w1, w3, w2)
            return jnp.einsum('eth,te->th', y, wh), \
                counts.astype('int32')

    def block(self, s):
        """Rows a held expert computes in one pass of a prefill of
        ``s`` tokens: what a router that spreads its choices evenly
        sends it (``s * top_k / experts``) and four standard deviations
        of that count, in whole tiles of 16 rows. A router that sends
        one expert more than this costs more passes, never a token."""
        mean = s * self.top_k / self.experts
        return int(-(-(mean + 4.0 * mean ** 0.5) // 16) * 16)

    def grouped(self, n, w, top_i, length, w1, w3, w2):
        """A prefill's expert layer: the assignments that landed on a
        held expert, sorted by expert, :meth:`block` rows of every held
        expert to a pass, in as many passes as the fullest expert
        needs: one batched product a pass reads each held expert's
        weights once. Rows at or past ``length`` are padding and are
        routed nowhere. Returns (routed (S, H), counts (held,))."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        s, k, eh = n.shape[0], self.top_k, len(self.held)
        m, cb = s * k, self.block(s)
        with jax.named_scope('router'):
            local = jnp.asarray(self.local_of)[top_i]        # (S, K)
            real = (jnp.arange(s) < length)[:, None]
            local = jnp.where(real, local, -1).reshape(m)
            here = local >= 0
            order = jnp.argsort(jnp.where(here, local, eh), stable=True)
            counts = jnp.sum(local[:, None] == jnp.arange(eh)[None],
                             axis=0).astype('int32')
            starts = jnp.cumsum(counts) - counts
            token_at = (order // k).astype('int32')
            # each (token, choice)'s place among its expert's rows
            rank = jnp.zeros(m, 'int32').at[order].set(
                jnp.arange(m, dtype='int32')) \
                - starts[jnp.maximum(local, 0)]
        with jax.named_scope('experts'):
            nb = n.astype(self.dtype)
            lane = jnp.arange(cb, dtype='int32')[None]

            def one_pass(j, acc):
                at = jnp.minimum(starts[:, None] + j * cb + lane, m - 1)
                y = self._ffn('ech,ehf->ecf', 'ecf,efh->ech',
                              nb[token_at[at]], w1, w3,
                              w2).astype(self.dtype)
                # a lane past its expert's count computed some other
                # expert's row: nothing picks it. What was routed
                # elsewhere, or comes in another pass, picks the zero row
                y = jnp.concatenate([y.reshape(eh * cb, self.hidden),
                                     jnp.zeros((1, self.hidden), y.dtype)])
                pick = jnp.where(here & (rank // cb == j),
                                 local * cb + rank % cb, eh * cb)
                return acc + jnp.einsum(
                    'skh,sk->sh',
                    y[pick.reshape(s, k)].astype('float32'), w)

            return lax.fori_loop(
                0, (jnp.max(counts) + cb - 1) // cb, one_pass,
                jnp.zeros((s, self.hidden), 'float32')), counts


def attend_blocks(q, k, v, block, window, dtype):
    """Causal attention of one whole sequence, a block of ``block``
    queries at a time: q (S, kv_heads, group, d) already scaled, k / v
    (S, kv_heads, d) -> (S, heads * d) float32. No (S, S) score tensor:
    a block scores against all S keys where ``window`` is None (a full
    layer), against the ``window + block`` keys that can be visible to
    it on a sliding layer."""
    import jax.numpy as jnp
    from jax import lax
    s = q.shape[0]
    blk = min(block, s)
    nblk = -(-s // blk)
    sp = nblk * blk
    q = jnp.pad(q, ((0, sp - s),) + ((0, 0),) * 3)
    k = jnp.pad(k, ((0, sp - s), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, sp - s), (0, 0), (0, 0)))
    span = sp if window is None else min(sp, window + blk)

    def one_block(i):
        qb = lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        start = jnp.clip((i + 1) * blk - span, 0, sp - span)
        kb = lax.dynamic_slice_in_dim(k, start, span, 0)
        vb = lax.dynamic_slice_in_dim(v, start, span, 0)
        qpos = i * blk + jnp.arange(blk)[:, None]
        kpos = start + jnp.arange(span)[None, :]
        seen = kpos <= qpos
        if window is not None:
            seen &= qpos - kpos < window
        scores = jnp.einsum('qkgd,lkd->kgql', qb, kb,
                            preferred_element_type='float32') \
            + jnp.where(seen, 0.0, -1e9)[None, None]
        att = softmax(scores).astype(dtype)
        return jnp.einsum('kgql,lkd->qkgd', att, vb,
                          preferred_element_type='float32')

    ctx = lax.map(one_block, jnp.arange(nblk))
    return ctx.reshape(sp, -1)[:s]


def attend_rows(q, keys, values, seen, dtype):
    """One query a slot over the rows its table gathered: q (S,
    kv_heads, group, d) already scaled, keys / values (S, L, kv_heads *
    d), seen (S, L) bool -> (S, heads * d) float32."""
    import jax.numpy as jnp
    s, length = keys.shape[:2]
    kv_heads, d = q.shape[1], q.shape[3]
    kh = keys.reshape(s, length, kv_heads, d)
    vh = values.reshape(s, length, kv_heads, d)
    scores = jnp.einsum('skgd,slkd->skgl', q, kh,
                        preferred_element_type='float32') \
        + jnp.where(seen, 0.0, -1e9)[:, None, None, :]
    att = softmax(scores).astype(dtype)
    return jnp.einsum('skgl,slkd->skgd', att, vh,
                      preferred_element_type='float32').reshape(s, -1)
