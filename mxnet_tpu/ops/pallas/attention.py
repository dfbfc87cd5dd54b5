"""Blockwise online-softmax (flash) attention kernels.

The roofline audit's #1 memory-bound cluster in the BERT step is the
attention softmax chain: XLA materializes the (B*H, S, S) scores,
exp/normalize, and attention-probability tensors through HBM between
the two batched GEMMs. This kernel computes the whole chain per
(batch*head, q-block) program with the scores resident in VMEM —
the only HBM traffic is q/k/v in and the context out.

The online-softmax math is the one already proven in
``parallel/ring_attention.py`` (running max + normalizer with -inf
masking and fully-masked-row guards); :func:`online_softmax_block` IS
that math, factored here so the ring recipe's per-device inner block
and this single-device VMEM kernel share one expression set — ring
attention rotates K/V blocks over ICI, this kernel walks them through
a VMEM loop.

Bit-identity structure (the decode engine contract): the key axis is
always processed in fixed blocks of ``K_BLOCK`` with padded/masked
keys contributing exact 0.0 to every reduction (exp(-inf - m) == 0.0
and x + 0.0 == x for finite x), so the padded-prefill pass, the
whole-sequence reference pass, and the cached decode step combine
identical reduction trees over the real keys — the same argument
``serving/decode/model.py`` makes for padded prefill, extended to
block boundaries. ``K_BLOCK`` must therefore stay the same across all
three paths (it is module-level, not a tuning parameter).

Backward is the standard flash recompute (dq / dkv kernels re-derive
the probability blocks from the saved log-sum-exp rather than loading
a stored attention matrix), wired through ``jax.custom_vjp``.

VMEM residency bound: each program holds its q block plus the full
per-head K/V rows (O(Sk * D) floats; the dkv pass symmetrically holds
the q/o/do rows, O(Sq * D)), so the *scores* never materialize but
K/V do — fine through Sk of a few thousand at D 64-128 against the
~16 MB/core budget, NOT an arbitrary-length kernel. Sequences past
that bound are the ring-attention recipe's job
(``parallel/ring_attention.py``), whose per-device inner block is
exactly this kernel's math over ICI-rotated K/V blocks; a manually
DMA-pipelined K walk (double-buffered ``make_async_copy``) is the
chip-side follow-up if single-device long-context ever needs it.

The paged decode walk (:func:`flash_paged_decode_attention`) is that
pipelined walk for the one case that needs it every step: a slot's
history lies in pages scattered over a pool, so the kernel takes the
page tables as scalars and copies a slot's live pages, and no others,
from HBM into two VMEM buffers in turn. Its blocks are its own
(``_walk_rows``, a whole number of pages), so its reduction tree is
not the dense kernels': it promises equality to rounding, not bits.

All kernels accept bf16/fp16 inputs and accumulate in float32 (AMP
composition); everything runs through the Pallas interpreter off-TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ['flash_attention', 'flash_decode_attention',
           'flash_paged_decode_attention', 'paged_walk_fits',
           'online_softmax_block', 'K_BLOCK']

# fixed key-axis block: part of the bit-identity contract (see module
# docstring) — every call path pads the key axis to a multiple of this
# and walks it in these steps
K_BLOCK = 128
# query-axis block: free to vary per call (query rows are independent)
_Q_BLOCK = 128
_NEG_INF = float('-inf')


def _cdiv(a, b):
    return -(-a // b)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = _cdiv(n, mult) * mult - n
    if not pad:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _online_block_cols(scores, v_blk, m, l, o):
    """One online-softmax accumulation step over a key block, with the
    per-row carries held as columns.

    ``scores``: (..., q, k) float32 with masked entries at exactly
    -inf; ``v_blk``: (..., k, d), float32 or the dtype the weights are
    rounded to for its product; carries ``m`` / ``l``
    (..., q, 1) and ``o`` (..., q, d). Fully-masked rows stay
    (m=-inf, l=0, o=0) — the caller divides by max(l, eps). The kernel
    bodies call this form directly: Mosaic lays vectors out as
    (sublane, lane) tiles and refuses most rank-1 shapes, so nothing
    inside a kernel is ever rank 1 (keepdims reductions throughout).
    """
    m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(jnp.where(jnp.isneginf(scores), _NEG_INF,
                          scores - safe_m))
    corr = jnp.exp(jnp.where(jnp.isneginf(m), _NEG_INF, m - safe_m))
    corr = jnp.where(jnp.isneginf(m), 0.0, corr)
    l_new = l * corr + p.sum(axis=-1, keepdims=True)
    batch = tuple(range(p.ndim - 2))
    o_new = o * corr + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk,
        (((p.ndim - 1,), (v_blk.ndim - 2,)), (batch, batch)),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def online_softmax_block(scores, v_blk, m, l, o):
    """:func:`_online_block_cols` with (..., q) carries — the form the
    ring-attention body uses (parallel/ring_attention.py); the ring
    rotates ``v_blk`` over ICI where this module's kernels walk VMEM
    blocks. One expression set for both."""
    m, l, o = _online_block_cols(scores, v_blk, m[..., None],
                                 l[..., None], o)
    return m[..., 0], l[..., 0], o


def _k_rows(j):
    """Key-axis slice of block ``j`` (aligned: lets Mosaic use
    unmasked sublane loads)."""
    return pl.ds(pl.multiple_of(j * K_BLOCK, K_BLOCK), K_BLOCK)


def _bias_blocks(bias):
    """(B, Sk_pad) additive key bias -> (B, nk, K_BLOCK): one row per
    key block, so a kernel picks block ``j`` with a sublane slice and
    every BlockSpec's last two dims equal the array's (the TPU
    lowering refuses a (1, Sk) block over a (B, Sk) array)."""
    b, sk = bias.shape
    return bias.reshape(b, sk // K_BLOCK, K_BLOCK)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def mxnet_tpu_flash_attention_fwd(q_ref, k_ref, v_ref, bias_ref,
                                  o_ref, lse_ref, *, nk, scale, causal,
                                  heads):
    """One (batch*head, q-block) program: walk the key axis in
    K_BLOCK steps with the (BQ, K_BLOCK) score tile in VMEM."""
    del heads  # folded into the bias index_map; kept for cost readers
    qb = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)
    bq, d = qb.shape
    qi = pl.program_id(1)
    q_pos = qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, K_BLOCK), 0)

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, _k_rows(j), :].astype(jnp.float32)
        vb = v_ref[0, _k_rows(j), :].astype(jnp.float32)
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + bias_ref[0, pl.ds(j, 1), :]
        if causal:
            k_pos = j * K_BLOCK + jax.lax.broadcasted_iota(
                jnp.int32, (bq, K_BLOCK), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        return _online_block_cols(s, vb, m, l, acc)

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
    safe_m = jnp.where(jnp.isneginf(m), 0.0, m)
    lse_ref[0] = jnp.where(l > 0,
                           safe_m + jnp.log(jnp.maximum(l, 1e-20)),
                           _NEG_INF)


def _fwd_call(q3, k3, v3, bias, *, heads, causal, scale, interpret):
    """q3/k3/v3: (B*H, S*, D) padded; bias: (B, nk, K_BLOCK) f32
    additive (-inf = blocked key). Returns (out (B*H, Sq_pad, D), lse
    (B*H, Sq_pad, 1) f32 — a column per row, like the carries)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    bq = min(_Q_BLOCK, sq)
    nq, nk = sq // bq, sk // K_BLOCK
    kern = functools.partial(mxnet_tpu_flash_attention_fwd, nk=nk,
                             scale=scale, causal=causal, heads=heads)
    h = heads
    return pl.pallas_call(
        kern,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nk, K_BLOCK), lambda b, i: (b // h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3, bias)


# ---------------------------------------------------------------------------
# backward kernels (flash recompute from the saved log-sum-exp)
# ---------------------------------------------------------------------------


def _p_block(qb, kb, bias_blk, lse, q_pos, k_pos, causal, scale):
    """Recompute one probability block p = exp(s - lse) with masked
    and fully-masked entries at exactly 0. ``bias_blk`` is a (1, K)
    row, ``lse`` a (Q, 1) column."""
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_blk
    if causal:
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    dead = jnp.isneginf(s) | jnp.isneginf(lse)
    return jnp.where(dead, 0.0, jnp.exp(s - jnp.where(
        jnp.isneginf(lse), 0.0, lse))), s


def mxnet_tpu_flash_attention_dq(q_ref, k_ref, v_ref, bias_ref,
                                 o_ref, lse_ref, do_ref, dq_ref, *,
                                 nk, scale, causal, heads):
    del heads
    qb = q_ref[0].astype(jnp.float32)
    dob = do_ref[0].astype(jnp.float32)
    ob = o_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                    # (BQ, 1)
    bq, d = qb.shape
    qi = pl.program_id(1)
    q_pos = qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, K_BLOCK), 0)
    delta = jnp.sum(dob * ob, axis=-1, keepdims=True)   # (BQ, 1)

    def body(j, dq):
        kb = k_ref[0, _k_rows(j), :].astype(jnp.float32)
        vb = v_ref[0, _k_rows(j), :].astype(jnp.float32)
        k_pos = j * K_BLOCK + jax.lax.broadcasted_iota(
            jnp.int32, (bq, K_BLOCK), 1)
        bias_blk = bias_ref[0, pl.ds(j, 1), :]
        p, _ = _p_block(qb, kb, bias_blk, lse, q_pos, k_pos, causal,
                        scale)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def mxnet_tpu_flash_attention_dkv(q_ref, k_ref, v_ref, bias_ref,
                                  o_ref, lse_ref, do_ref, dk_ref,
                                  dv_ref, *, nq, bq, scale, causal,
                                  heads):
    del heads
    kb = k_ref[0].astype(jnp.float32)                   # (BK, D)
    vb = v_ref[0].astype(jnp.float32)
    bk, d = kb.shape
    kj = pl.program_id(1)
    bias_blk = bias_ref[0, pl.ds(kj, 1), :]             # (1, BK)
    k_pos = kj * bk + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 1)

    def body(i, carry):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
        qb = q_ref[0, rows, :].astype(jnp.float32)
        dob = do_ref[0, rows, :].astype(jnp.float32)
        ob = o_ref[0, rows, :].astype(jnp.float32)
        lse = lse_ref[0, rows, :]                       # (BQ, 1)
        q_pos = i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        p, _ = _p_block(qb, kb, bias_blk, lse, q_pos, k_pos, causal,
                        scale)
        dv = dv + jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = jnp.sum(dob * ob, axis=-1, keepdims=True)
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nq, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_call(q3, k3, v3, bias, o3, lse, do3, *, heads, causal, scale,
              interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    bq = min(_Q_BLOCK, sq)
    nq, nk = sq // bq, sk // K_BLOCK
    h = heads
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    q_full = pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0),
                          memory_space=pltpu.VMEM)
    k_full = pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, K_BLOCK, d), lambda b, j: (b, j, 0),
                          memory_space=pltpu.VMEM)
    # the whole (nk, K_BLOCK) bias of the program's batch row; the dkv
    # kernel picks its key block's row by program id
    bias_spec = pl.BlockSpec((1, nk, K_BLOCK),
                             lambda b, i: (b // h, 0, 0),
                             memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(mxnet_tpu_flash_attention_dq, nk=nk,
                          scale=scale, causal=causal, heads=heads),
        grid=(bh, nq),
        in_specs=[
            q_spec, k_full, k_full, bias_spec,
            q_spec,
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            q_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        interpret=interpret,
    )(q3, k3, v3, bias, o3, lse, do3)
    dk, dv = pl.pallas_call(
        functools.partial(mxnet_tpu_flash_attention_dkv, nq=nq, bq=bq,
                          scale=scale, causal=causal, heads=heads),
        grid=(bh, nk),
        in_specs=[
            q_full, k_spec, k_spec, bias_spec,
            q_full,
            pl.BlockSpec((1, sq, 1), lambda b, j: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            q_full,
        ],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v3.dtype)],
        interpret=interpret,
    )(q3, k3, v3, bias, o3, lse, do3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper over padded (B, H, S, D) arrays
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_core(q, k, v, bias, causal, scale, interpret):
    """q/k/v: (B, H, Sq_pad, D) / (B, H, Sk_pad, D); bias
    (B, nk, K_BLOCK) f32 additive with -inf on blocked keys."""
    out, _ = _flash_fwd_impl(q, k, v, bias, causal, scale, interpret)
    return out


def _flash_fwd_impl(q, k, v, bias, causal, scale, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    o3, lse = _fwd_call(q.reshape(b * h, sq, d),
                        k.reshape(b * h, sk, d),
                        v.reshape(b * h, sk, d), bias, heads=h,
                        causal=causal, scale=scale, interpret=interpret)
    return o3.reshape(b, h, sq, d), lse


def _flash_fwd(q, k, v, bias, causal, scale, interpret):
    out, lse = _flash_fwd_impl(q, k, v, bias, causal, scale, interpret)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(causal, scale, interpret, res, g):
    q, k, v, bias, out, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    dq, dk, dv = _bwd_call(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
        v.reshape(b * h, sk, d), bias,
        out.reshape(b * h, sq, d), lse, g.reshape(b * h, sq, d),
        heads=h, causal=causal, scale=scale, interpret=interpret)
    return (dq.reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape), jnp.zeros_like(bias))


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, lengths=None, causal=False, scale=None):
    """Blockwise flash attention over (B, H, S, D) arrays.

    ``lengths`` (B,) masks keys at positions >= length (the padded-
    prefill / valid-length form — exactly 0.0 attention weight, the
    bit-identity contract); ``causal`` adds the autoregressive mask.
    ``scale`` defaults to 1/sqrt(D). Returns (B, H, Sq, D) in the
    input dtype; float32 accumulation inside the kernel.
    """
    from . import interpret_mode
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    sk_pad = _cdiv(sk, K_BLOCK) * K_BLOCK
    kp = _pad_to(k, 2, K_BLOCK)
    vp = _pad_to(v, 2, K_BLOCK)
    bq = min(_Q_BLOCK, max(8, _cdiv(sq, 8) * 8))
    qp = _pad_to(q, 2, bq)
    k_pos = jnp.arange(sk_pad)
    if lengths is None:
        valid = k_pos[None, :] < sk
    else:
        # lengths: scalar or (B,) — either broadcasts over the batch
        valid = (k_pos[None, :] < jnp.reshape(
            jnp.asarray(lengths), (-1, 1))) & (k_pos[None, :] < sk)
    valid = jnp.broadcast_to(valid, (b, sk_pad))
    bias = jnp.where(valid, 0.0, _NEG_INF).astype(jnp.float32)
    out = _flash_core(qp, kp, vp, _bias_blocks(bias), bool(causal),
                      float(scale), interpret_mode())
    return out[:, :, :sq, :]


# ---------------------------------------------------------------------------
# single-token decode variant: reads the slot KV cache in its native
# (slots, max_len, units) layout — no per-step head transpose of the
# cache, which is the per-token cache-traffic win
# ---------------------------------------------------------------------------


def mxnet_tpu_flash_decode_fwd(q_ref, k_ref, v_ref, bias_ref, o_ref,
                               *, nk, heads, scale):
    """One slot per program: the single query row attends its own
    cache prefix. Per head: (8, D) x (K_BLOCK, D) dots (row 0 real,
    rows 1-7 padding) — the same dot_general shapes and the same
    K_BLOCK walk as the full kernel, so the reduction tree over the
    real keys is identical (the decode bit-identity contract)."""
    u = q_ref.shape[-1]
    d = u // heads

    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)
        qh = q_ref[0, :, lanes].astype(jnp.float32) * scale   # (8, D)

        def body(j, carry, qh=qh, lanes=lanes):
            m, l, acc = carry
            kb = k_ref[0, _k_rows(j), lanes].astype(jnp.float32)
            vb = v_ref[0, _k_rows(j), lanes].astype(jnp.float32)
            s = jax.lax.dot_general(
                qh, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s + bias_ref[0, pl.ds(j, 1), :]
            return _online_block_cols(s, vb, m, l, acc)

        m0 = jnp.full((8, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((8, 1), jnp.float32)
        a0 = jnp.zeros((8, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
        o_ref[0, :, lanes] = (acc / jnp.maximum(l, 1e-20)).astype(
            o_ref.dtype)


def flash_decode_attention(q, keys, values, positions, heads,
                           scale=None):
    """Cached decode-step attention: ``q`` (slots, U) single-token
    queries against the slot cache ``keys``/``values``
    (slots, max_len, U); each slot attends its own prefix
    (k_pos <= positions[slot]). Returns (slots, U) context.

    Forward-only by design (the decode step never backpropagates);
    grads, if ever requested, raise at transpose time.
    """
    from . import interpret_mode
    slots, u = q.shape
    max_len = keys.shape[1]
    d = u // heads
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    lp = _cdiv(max_len, K_BLOCK) * K_BLOCK
    kp = _pad_to(keys, 1, K_BLOCK)
    vp = _pad_to(values, 1, K_BLOCK)
    k_pos = jnp.arange(lp)
    valid = (k_pos[None, :] <= positions[:, None]) & \
        (k_pos[None, :] < max_len)
    bias = _bias_blocks(
        jnp.where(valid, 0.0, _NEG_INF).astype(jnp.float32))
    # pad the single query row to the f32 sublane tile (8)
    q8 = jnp.pad(q[:, None, :], ((0, 0), (0, 7), (0, 0)))
    from jax.experimental import pallas as pl_mod
    from jax.experimental.pallas import tpu as pltpu
    nk = lp // K_BLOCK
    out = pl_mod.pallas_call(
        functools.partial(mxnet_tpu_flash_decode_fwd, nk=nk,
                          heads=heads, scale=float(scale)),
        grid=(slots,),
        in_specs=[
            pl_mod.BlockSpec((1, 8, u), lambda s: (s, 0, 0),
                             memory_space=pltpu.VMEM),
            pl_mod.BlockSpec((1, lp, u), lambda s: (s, 0, 0),
                             memory_space=pltpu.VMEM),
            pl_mod.BlockSpec((1, lp, u), lambda s: (s, 0, 0),
                             memory_space=pltpu.VMEM),
            pl_mod.BlockSpec((1, nk, K_BLOCK), lambda s: (s, 0, 0),
                             memory_space=pltpu.VMEM),
        ],
        out_specs=pl_mod.BlockSpec((1, 8, u), lambda s: (s, 0, 0),
                                   memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((slots, 8, u), q.dtype),
        interpret=interpret_mode(),
    )(q8, kp, vp, bias)
    return out[:, 0, :]


# ---------------------------------------------------------------------------
# single-token decode over a PAGED cache: the kernel walks the page
# table itself and reads a sequence's live pages from the pool where
# they lie — no per-slot view of the history is written to HBM
# ---------------------------------------------------------------------------

# VMEM the walk's page buffers may fill: two buffers a pool, each one
# compute block of rows. A block is the largest power of two of rows
# that fits, in whole pages (one page where a page is larger). On a v5e
# that is 256 rows for GPT-1's and Granite's two pools (GPT-1's tables
# read alike at 128 and 256, Granite's longer ones 11 % faster at 256
# and no faster at 512: PERF.md section 6, PR 35) and 1 024 for the one
# pool of 640 bfloat16 columns (48 slots of about 8 200 rows: 2 235 /
# 1 720 / 1 446 / 1 356 us a call at 128 / 256 / 512 / 1 024 rows: what
# a block costs beside its rows is 1.0 us; PERF.md section 6, PR 36).
# The dense kernels' K_BLOCK is their bit-identity contract and stays
# theirs
_WALK_VMEM_BYTES = 3 << 20


def _walk_rows(pools, width, dtype):
    fit = _WALK_VMEM_BYTES // (2 * pools * width
                               * jnp.dtype(dtype).itemsize)
    return 1 << (fit.bit_length() - 1)


def walk_block_pages(pools, page_size, width, dtype):
    """Pages of one compute block of the walk over ``pools`` pools of
    ``width`` columns of ``dtype``."""
    return max(1, _walk_rows(pools, width, dtype) // page_size)


# Pages one copy moves where a table's entries are consecutive. A page
# copied alone costs 37.5 ns to issue and wait for, whatever its bytes,
# and the scalar loops that do it stand in front of the contractions:
# at 20 KB a page that is 2.35 ns a row where the bytes are 1.56. Copies
# of 2 / 4 / 8 / 16 pages all move the bytes at 735 GB/s, one copy of a
# whole block of 64 is 5 % slower (PERF.md section 6, PR 37)
_RUN_PAGES = 8


def walk_copy_runs(xp, tables, positions, page_size, block_pages,
                   trash_page):
    """The walk's copy schedule, read off the tables: which aligned
    chunks of ``_RUN_PAGES`` table columns one copy a pool can move.

    Chunk ``c`` of slot ``s`` is a run where its entries are
    consecutive pages (``tables[s, i + 1] == tables[s, i] + 1`` across
    it) and all of it lies at or below the slot's last live page; every
    other live page is copied alone, so any table is served and the
    result does not depend on where pages lie. ``xp`` is ``jax.numpy``
    (the kernel's flags, computed beside it) or ``numpy``
    (``PageOwner.step_copies``' count): one statement of the rule.

    Returns ``(run, copies)``: ``run`` (slots, whole chunks of the
    table) bool; ``copies`` (slots,) the copies a pool that the slot's
    walk issues: its live pages (none for an empty slot: first entry
    the trash page), each run counted once."""
    chunk = min(_RUN_PAGES, block_pages)
    whole = tables.shape[1] // chunk
    tops = xp.where(tables[:, 0] != trash_page,
                    positions // page_size + 1, 0)
    inside = tables[:, :whole * chunk]     # a table's tail is no run
    follows = (inside[:, 1:] - inside[:, :-1]) == 1
    run = (xp.arange(1, whole + 1) * chunk)[None] <= tops[:, None]
    for i in range(chunk - 1):
        run = run & follows[:, i::chunk]
    return run, tops - (chunk - 1) * run.sum(-1)


def mxnet_tpu_paged_decode_walk(tables_ref, pos_ref, next_ref, runs_ref,
                                q_ref, *refs, page_size, block_pages,
                                max_pages, group_rows, group_width,
                                trash_page):
    """One slot per program: the slot's query rows attend the pages its
    table names, up to its position and no further.

    ``tables_ref`` (slots * max_pages,), ``pos_ref`` (slots,),
    ``next_ref`` (slots + 1,: the first live slot at or after each
    index) and ``runs_ref`` (slots * blocks,: :func:`walk_copy_runs`'
    bits) are scalar-prefetched. ``refs``: the pools (pages,
    page_size, width), left where they are; the output; a VMEM buffer a
    pool, two blocks in the pool's own (pages, page_size, width) shape;
    the copies' semaphores and the buffer parity. Keys are read
    from the first pool and values from the last one's leading columns,
    as many as the output is wide: with a K and a V pool those are all
    of V's, with one pool (latent rows) a row's values are its own
    leading columns, taken from the one VMEM copy of the page that its
    keys are read from. A slot at position
    ``p`` walks ``p // page_size + 1`` pages in blocks of
    ``block_pages``: copies into one of two VMEM buffers, the next
    block's in flight while this block computes, across the slot
    boundary too (``parity_ref`` carries the buffer in use from one
    program to the next; the grid runs in order). A chunk of
    ``_RUN_PAGES`` consecutive pages is one copy a pool and one wait,
    any other page a copy of its own. A slot whose first table entry is
    the trash page is empty: it copies nothing and writes zeros.

    No head is split out of a page. ``q_ref`` (1, rep, width) holds, in
    row ``r``, the queries of the ``groups`` heads that are the
    ``r``-th of their group of columns, each in its own group's
    columns (``group_rows``: the groups, padded to whole sublane
    tiles; 1 where every head reads the whole row); the kernel lays
    head ``(r, g)`` over all ``width``
    columns, zero outside group ``g``, so both contractions run over
    the rows as they lie in the pool, and each column of the context
    keeps its own head's sum. Rows past the position get weight
    exactly 0 and their values are never multiplied (a select, not a
    product: what lies there may be anything)."""
    pools = (len(refs) - 3) // 2
    hbm, o_ref, bufs = refs[:pools], refs[pools], refs[pools + 1:-2]
    sems, parity_ref = refs[-2:]
    kbuf, vbuf = bufs[0], bufs[-1]
    s = pl.program_id(0)
    nslots = pl.num_programs(0)
    ps, rows = page_size, block_pages * page_size
    rep, width = q_ref.shape[1], q_ref.shape[2]
    out_width = o_ref.shape[2]

    run_pages = min(_RUN_PAGES, block_pages)
    blocks = _cdiv(max_pages, block_pages)

    def block_copies(fn, slot, j, buf):
        """``fn`` on every pool's every copy of block ``j`` of
        ``slot``, into (or, waiting, out of) buffer ``buf``."""
        first = j * block_pages
        count = jnp.minimum(block_pages,
                            pos_ref[slot] // ps + 1 - first)
        runs = runs_ref[slot * blocks + j]

        def copy(at, pages):
            page = tables_ref[slot * max_pages + first + at]
            for i in range(pools):
                fn(pltpu.make_async_copy(
                    hbm[i].at[pl.ds(page, pages)],
                    bufs[i].at[buf, pl.ds(at, pages)], sems.at[buf, i]))

        def one_page(p, _):
            copy(p, 1)
            return _

        def one_chunk(c, _):
            at = pl.multiple_of(c * run_pages, run_pages)
            run = (runs >> c) & 1 == 1

            @pl.when(run)
            def _run():
                copy(at, run_pages)

            @pl.when(jnp.logical_not(run))
            def _pages():
                jax.lax.fori_loop(
                    at, jnp.minimum(at + run_pages, count), one_page, 0)
            return _

        jax.lax.fori_loop(0, (count + run_pages - 1) // run_pages,
                          one_chunk, 0)

    def start(slot, j, buf):
        @pl.when(slot < nslots)
        def _():
            block_copies(lambda c: c.start(), slot, j, buf)

    @pl.when(s == 0)
    def _():
        parity_ref[0] = 0
        start(next_ref[0], 0, 0)

    live = tables_ref[s * max_pages] != trash_page

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(live)
    def _():
        pos = pos_ref[s]
        nblocks = pos // rows + 1
        heads_rows = rep * group_rows
        col = jax.lax.broadcasted_iota(jnp.int32, (group_rows, width), 1)
        low = jax.lax.broadcasted_iota(
            jnp.int32, (group_rows, width), 0) * group_width
        own = (col >= low) & (col < low + group_width)   # (G, width)
        qx = jnp.concatenate(
            [jnp.where(own, q_ref[0, r:r + 1, :], 0.0)
             for r in range(rep)], axis=0).astype(kbuf.dtype)

        def block(j, carry, then_slot, then_block, masked):
            """Block ``j``: the next copies (this slot's next block, or
            the next live slot's first) go out before this block's are
            waited for. Only a slot's last block has rows past its
            position: the selects are taken there alone."""
            m, l, acc, buf = carry
            start(then_slot, then_block, 1 - buf)
            block_copies(lambda c: c.wait(), s, j, buf)
            kb = kbuf[buf].reshape(rows, width)
            vb = vbuf[buf].reshape(rows, width)[:, :out_width]
            sc = jax.lax.dot_general(
                qx, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (heads, rows)
            if masked:
                at = j * rows + jax.lax.broadcasted_iota(
                    jnp.int32, (heads_rows, rows), 1)
                sc = jnp.where(at <= pos, sc, _NEG_INF)
                seen = j * rows + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, 1), 0) <= pos
                vb = jnp.where(seen, vb, jnp.zeros_like(vb))
            m, l, acc = _online_block_cols(sc, vb, m, l, acc)
            return m, l, acc, 1 - buf

        m0 = jnp.full((heads_rows, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((heads_rows, 1), jnp.float32)
        a0 = jnp.zeros((heads_rows, out_width), jnp.float32)
        carry = jax.lax.fori_loop(
            0, nblocks - 1,
            lambda j, carry: block(j, carry, s, j + 1, False),
            (m0, l0, a0, parity_ref[0]))
        _m, l, acc, buf = block(nblocks - 1, carry, next_ref[s + 1], 0, True)
        parity_ref[0] = buf
        ctx = acc / jnp.maximum(l, 1e-20)
        for r in range(rep):
            mine = ctx[r * group_rows:(r + 1) * group_rows]
            o_ref[0, r:r + 1, :] = jnp.sum(
                jnp.where(own[:, :out_width], mine, 0.0), axis=0,
                keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('heads', 'scale', 'interpret',
                                             'value_cols'))
def _paged_walk(q, key_pool, value_pool, tables, positions, *, heads,
                scale, interpret, value_cols=None):
    """:func:`flash_paged_decode_attention` behind a ``jit`` of its own:
    the layers of one step call it with the same shapes, so the kernel
    is traced, lowered and serialized once a step program, not once a
    layer (12 Mosaic modules cost a GPT-1 process seconds of set-up)."""
    from ...serving.decode.paged import TRASH_PAGE
    slots, qw = q.shape
    _pages, ps, width = key_pool.shape
    d = qw // heads
    groups = width // d
    rep = heads // groups
    max_pages = tables.shape[1]
    # one pool: a row's values are its own leading columns
    pools = (key_pool,) if value_pool is None else (key_pool, value_pool)
    block_pages = walk_block_pages(len(pools), ps, width, key_pool.dtype)
    # sublane tiles: a group's heads fill whole float32 tiles, and the
    # heads together whole tiles of the pool's dtype
    tile = _sublane_tile(key_pool.dtype)
    group_rows = _cdiv(groups, 8) * 8
    if rep * group_rows % tile:
        group_rows = _cdiv(groups, tile) * tile
    if groups == 1 and rep % tile == 0:
        group_rows = 1            # every head reads the whole row
    out_width = value_cols or width
    # row r: the r-th head of every group, each in its group's columns
    q3 = (q.astype(jnp.float32) * scale).reshape(
        slots, groups, rep, d).transpose(0, 2, 1, 3).reshape(
            slots, rep, width)
    live = tables[:, 0] != TRASH_PAGE
    first_live_from = jax.lax.cummin(
        jnp.where(live, jnp.arange(slots, dtype=jnp.int32), slots),
        reverse=True)
    nxt = jnp.concatenate(
        [first_live_from, jnp.full((1,), slots, jnp.int32)])
    # bit c of block j: chunk c of it is a run
    run, _copies = walk_copy_runs(jnp, tables, positions, ps, block_pages,
                                  TRASH_PAGE)
    per_block = block_pages // min(_RUN_PAGES, block_pages)
    blocks = _cdiv(max_pages, block_pages)
    runs = jnp.sum(
        jnp.pad(run, ((0, 0), (0, blocks * per_block - run.shape[1])))
        .reshape(slots, blocks, per_block).astype(jnp.int32)
        << jnp.arange(per_block, dtype=jnp.int32), axis=-1)
    kern = functools.partial(
        mxnet_tpu_paged_decode_walk, page_size=ps,
        block_pages=block_pages, max_pages=max_pages,
        group_rows=group_rows, group_width=d, trash_page=TRASH_PAGE)

    def rows_of(w):
        return pl.BlockSpec((1, rep, w), lambda s, *_: (s, 0, 0),
                            memory_space=pltpu.VMEM)

    buf = pltpu.VMEM((2, block_pages, ps, width), key_pool.dtype)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots,),
            in_specs=[rows_of(width)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=rows_of(out_width),
            scratch_shapes=[buf] * len(pools)
            + [pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, rep, out_width),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        name='mxnet_tpu_paged_decode_walk',
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32),
      nxt, runs.reshape(-1), q3, *pools)
    if value_pool is None:
        return out.reshape(slots, heads * out_width)
    return out.reshape(slots, rep, groups, d).transpose(
        0, 2, 1, 3).reshape(slots, heads * d)


def flash_paged_decode_attention(q, key_pool, value_pool, tables,
                                 positions, heads, scale=None,
                                 value_cols=None):
    """Decode-step attention over a PAGED KV cache, read through the
    page table inside one kernel: ``q`` (slots, heads * d) single-token
    queries; ``key_pool`` / ``value_pool`` (pages, page_size, width) —
    the shared pools every sequence's pages live in, ``width`` a whole
    number of groups of ``d`` columns, ``heads`` a whole number of
    heads a group (one for plain multi-head attention, several for
    grouped queries: head ``h`` reads group ``h // (heads // groups)``);
    ``tables`` (slots, max_pages) int32 under ``paged.gather_pages``'
    contract; ``positions`` (slots,). Returns the context (slots,
    heads * d) in float32.

    Each slot attends rows ``0 .. positions[slot]`` of its own pages
    and reads no other: the work follows the live pages, not ``slots x
    max_pages``. A slot whose first table entry is the trash page is
    empty and gets zeros. The softmax is online over blocks of
    ``_walk_rows`` rows with float32 carries; the contractions take
    their operands in the pool's dtype, accumulate in float32 and run
    at the default precision of where they are placed, as the gathered
    view's did. The reduction tree is this kernel's own: equal to
    ``gather_pages`` + dense softmax to rounding, not bit for bit
    (docs/DIVERGENCES.md).

    The latent geometry: ``value_pool`` None and ``value_cols`` given.
    A row of ``key_pool`` is the key of every head (``q`` is (slots,
    heads * width): one group of ``width`` columns) and its leading
    ``value_cols`` columns are the values: each page is copied once and
    read as both. Returns the context (slots, heads * value_cols).

    Mosaic wants ``page_size`` a whole number of the pool dtype's
    sublane tiles and ``width`` of 128 lanes (:func:`paged_walk_fits`);
    the interpreter takes any shape."""
    from . import interpret_mode
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[1] // heads)
    if (value_pool is None) != (value_cols is not None):
        raise ValueError('a value pool, or the key pool\'s value_cols')
    return _paged_walk(q, key_pool, value_pool, tables, positions,
                       heads=int(heads), scale=float(scale),
                       interpret=interpret_mode(),
                       value_cols=None if value_cols is None
                       else int(value_cols))


def _sublane_tile(dtype):
    """Rows of one (sublane, lane) tile of ``dtype``: 8 of 4 bytes."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def paged_walk_fits(page_size, width, dtype):
    """Whether Mosaic takes :func:`flash_paged_decode_attention` at this
    pool geometry: a page is a whole number of the dtype's sublane
    tiles and a row a whole number of 128 lanes, so that a page copy
    lands on tile boundaries."""
    return page_size % _sublane_tile(dtype) == 0 and width % 128 == 0


# module-level pl import for the kernel bodies (resolved lazily at
# trace time would shadow per-call; kernels only run under pallas_call)
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402
