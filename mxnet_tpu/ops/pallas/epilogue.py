"""Fused elementwise epilogue kernels: normalize / activation /
residual-add in one VMEM pass.

The ResNet step's audit-ranked byte movers after the convs are the
BatchNorm apply + ReLU + residual-add chains: each is an activation-
sized read-modify-write XLA schedules as separate loop fusions with
HBM between them when the producing conv's tiling does not line up.
These kernels pin the whole epilogue to one read and one write:

  * :func:`fused_bn_apply` — ``out = act((x - mean) * scale + beta)``
    where ``scale = gamma * rsqrt(var + eps)`` — tiny per-channel
    vectors computed on the host side of the kernel (inference
    BatchNorm and the training-forward normalize both reduce to this
    affine apply once the statistics are in hand);
  * :func:`fused_act` — the save-output activation core
    (``ops/nn.py`` ``_act_core``) as a kernel: forward emits act(x),
    backward derives the local gradient from the OUTPUT alone (same
    residual contract, same closed forms);
  * :func:`fused_add_act` — residual add + activation
    (``relu(x + shortcut)``, the v1 ResNet block join).

Layout strategy: every kernel flattens its operand to 2-D
``(rows, cols)`` and grids over row blocks, so VMEM residency is one
(row-block, cols) tile regardless of the tensor's true rank — the
per-(sample, channel) affine coefficients ride along as a
``(rows, 1)`` column. bf16/fp16 inputs compute in float32 and emit
the input dtype (AMP composition).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ['fused_bn_apply', 'fused_act', 'fused_add_act']

_ROW_BLOCK = 256
# elements of one (row-block, col-block) tile: 2 MiB in float32. Mosaic
# double-buffers every operand and the kernels compute in float32, so a
# tile this size keeps the whole pipeline well inside the 16 MiB scoped
# VMEM limit (a (256, 12544) bf16 tile — ResNet-50's first BatchNorm at
# batch 128 — needs 24.5 MiB and is refused on a v5e).
_TILE_ELEMS = 512 * 1024


def _cdiv(a, b):
    return -(-a // b)


def _row_block(rows, cols):
    """Rows per tile: ``_ROW_BLOCK``, shrunk (to a multiple of 16, the
    bf16 sublane tile) when a row is so wide and unsplittable that 256
    of them overflow the tile budget."""
    br = min(_ROW_BLOCK, rows)
    if cols % 128 and br * cols > _TILE_ELEMS:
        br = max(16, (_TILE_ELEMS // cols) // 16 * 16)
    return br


def _col_block(cols, br):
    """Widest column block that keeps a ``br``-row tile within
    ``_TILE_ELEMS``: the whole row when it fits or cannot be split on
    the 128-lane tile, else the largest divisor of ``cols`` that is a
    multiple of 128."""
    if br * cols <= _TILE_ELEMS or cols % 128:
        return cols
    lanes = cols // 128
    return 128 * max(k for k in range(1, lanes + 1)
                     if lanes % k == 0 and br * k * 128 <= _TILE_ELEMS)


def _act_apply(x, act_type, slope):
    """Forward activations available inside the kernels — must stay
    expression-identical to ``ops/nn.py`` ``_act_forward`` for the
    covered types so knob flips only move bytes, not math."""
    if act_type is None or act_type == 'identity':
        return x
    if act_type == 'relu':
        return jax.nn.relu(x)
    if act_type == 'sigmoid':
        return jax.nn.sigmoid(x)
    if act_type == 'tanh':
        return jnp.tanh(x)
    if act_type == 'softrelu':
        return jax.nn.softplus(x)
    if act_type == 'softsign':
        return jax.nn.soft_sign(x)
    if act_type == 'leaky':
        return jnp.where(x >= 0, x, slope * x)
    raise ValueError('unsupported epilogue act_type %r' % (act_type,))


def _act_grad_from_out(out, act_type, slope):
    """d act/d x from the output alone — the ``ops/nn.py``
    ``_act_grad_from_out`` closed forms for the kernel-covered set."""
    one = jnp.ones_like(out)
    if act_type is None or act_type == 'identity':
        return one
    if act_type == 'relu':
        return (out > 0).astype(out.dtype)
    if act_type == 'sigmoid':
        return out * (1 - out)
    if act_type == 'tanh':
        return 1 - out * out
    if act_type == 'softrelu':
        return 1 - jnp.exp(-out)
    if act_type == 'softsign':
        a = 1 - jnp.abs(out)
        return a * a
    if act_type == 'leaky':
        return jnp.where(out >= 0, one, slope * one)
    raise ValueError('unsupported epilogue act_type %r' % (act_type,))


def _rows_call(kernel, outs, interpret, *arrays):
    """Grid a tiled elementwise kernel over 2-D operands. Every
    operand is (R, C) or an (R, 1) per-row coefficient column; outputs
    follow ``outs`` (list of (cols, dtype)). R must already be padded
    to a multiple of ``_row_block(R, C)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    r, c = arrays[0].shape
    br = _row_block(r, c)
    bc = _col_block(c, br)

    def spec(cols):
        if cols == 1:   # per-row coefficient: the same for every column tile
            return pl.BlockSpec((br, 1), lambda i, j: (i, 0),
                                memory_space=pltpu.VMEM)
        return pl.BlockSpec((br, bc), lambda i, j: (i, j),
                            memory_space=pltpu.VMEM)

    out_shape = [jax.ShapeDtypeStruct((r, cols), dt) for cols, dt in outs]
    out_specs = [spec(cols) for cols, _ in outs]
    single = len(outs) == 1
    return pl.pallas_call(
        kernel, grid=(r // br, c // bc),
        in_specs=[spec(a.shape[1]) for a in arrays],
        out_specs=out_specs[0] if single else out_specs,
        out_shape=out_shape[0] if single else out_shape,
        interpret=interpret,
    )(*arrays)


def _pad_rows(x, br):
    r = x.shape[0]
    pad = _cdiv(r, br) * br - r
    return (jnp.pad(x, ((0, pad), (0, 0))), r) if pad else (x, r)


# ---------------------------------------------------------------------------
# fused affine-normalize (+ activation): the BatchNorm apply epilogue
# ---------------------------------------------------------------------------


def mxnet_tpu_bn_act_fwd(x_ref, scale_ref, mean_ref, beta_ref,
                         o_ref, *, act_type, slope):
    xf = x_ref[...].astype(jnp.float32)
    # (x - mean) * scale + beta: the exact expression order of the
    # XLA path in ops/nn.py (_bn_train_fwd_impl), so knob flips move
    # bytes, not rounding
    y = (xf - mean_ref[...].astype(jnp.float32)) \
        * scale_ref[...].astype(jnp.float32) \
        + beta_ref[...].astype(jnp.float32)
    o_ref[...] = _act_apply(y, act_type, slope).astype(o_ref.dtype)


def mxnet_tpu_bn_act_bwd(g_ref, out_ref, scale_ref, dx_ref, *,
                         act_type, slope):
    gf = g_ref[...].astype(jnp.float32)
    out = out_ref[...].astype(jnp.float32)
    dx = gf * _act_grad_from_out(out, act_type, slope) \
        * scale_ref[...].astype(jnp.float32)
    dx_ref[...] = dx.astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _bn_apply_core(x2, scale_col, mean_col, beta_col, act_type, slope,
                   interpret):
    """x2 (R, C) with per-row affine columns (R, 1)."""
    kern = functools.partial(mxnet_tpu_bn_act_fwd, act_type=act_type,
                             slope=slope)
    return _rows_call(kern, [(x2.shape[1], x2.dtype)], interpret,
                      x2, scale_col, mean_col, beta_col)


def _bn_apply_fwd(x2, scale_col, mean_col, beta_col, act_type, slope,
                  interpret):
    out = _bn_apply_core(x2, scale_col, mean_col, beta_col, act_type,
                         slope, interpret)
    return out, (out, scale_col, mean_col, x2)


def _bn_apply_bwd(act_type, slope, interpret, res, g):
    out, scale_col, mean_col, x2 = res
    kern = functools.partial(mxnet_tpu_bn_act_bwd, act_type=act_type,
                             slope=slope)
    dx = _rows_call(kern, [(out.shape[1], x2.dtype)], interpret,
                    g, out, scale_col)
    # coefficient gradients: row reductions outside the kernel (tiny
    # vs the activation tensor; XLA fuses them with dx's producer)
    gf = g.astype(jnp.float32)
    local = gf * _act_grad_from_out(out.astype(jnp.float32), act_type,
                                    slope)
    cen = x2.astype(jnp.float32) - mean_col.astype(jnp.float32)
    dscale = jnp.sum(local * cen, axis=1, keepdims=True)
    dmean = -jnp.sum(local, axis=1, keepdims=True) \
        * scale_col.astype(jnp.float32)
    dbeta = jnp.sum(local, axis=1, keepdims=True)
    # the coefficient columns are all f32 by construction (col() casts
    # them); dbeta must match beta_col's dtype, NOT the data's
    return (dx, dscale.astype(scale_col.dtype),
            dmean.astype(mean_col.dtype), dbeta.astype(scale_col.dtype))


_bn_apply_core.defvjp(_bn_apply_fwd, _bn_apply_bwd)


def fused_bn_apply(x, scale, mean, beta, axis=1, act_type=None,
                   slope=0.0):
    """``act((x - mean) * scale + beta)`` with per-``axis``
    coefficients in one VMEM pass (``scale = gamma * rsqrt(var +
    eps)``). Covers the inference BatchNorm apply and the training-
    forward normalize; the expression order matches the XLA path in
    ``ops/nn.py`` so the kernel moves bytes, not rounding."""
    from . import interpret_mode
    ax = axis % x.ndim
    # flatten so the channel axis lands in the row index and each row
    # carries one (scale, mean, beta) coefficient triple
    perm = (0, ax) + tuple(i for i in range(1, x.ndim) if i != ax) \
        if ax != 0 else tuple(range(x.ndim))
    xt = jnp.transpose(x, perm) if perm != tuple(range(x.ndim)) else x
    lead = xt.shape[:2] if ax != 0 else xt.shape[:1]
    rows = 1
    for s in lead:
        rows *= s
    x2 = xt.reshape(rows, -1)
    c = scale.shape[0]

    def col(vec):
        v32 = vec.astype(jnp.float32)
        if ax == 0:
            return v32.reshape(-1, 1)
        return jnp.broadcast_to(v32.reshape(1, c, 1),
                                (xt.shape[0], c, 1)).reshape(-1, 1)

    br = _row_block(rows, x2.shape[1])
    x2p, r = _pad_rows(x2, br)
    cols = [_pad_rows(col(v), br)[0] for v in (scale, mean, beta)]
    out = _bn_apply_core(x2p, cols[0], cols[1], cols[2], act_type,
                         float(slope), interpret_mode())[:r]
    out = out.reshape(xt.shape)
    if perm != tuple(range(x.ndim)):
        inv = [0] * x.ndim
        for i, p in enumerate(perm):
            inv[p] = i
        out = jnp.transpose(out, inv)
    return out


# ---------------------------------------------------------------------------
# save-output activation core (the _act_core kernel twin)
# ---------------------------------------------------------------------------


def mxnet_tpu_act_fwd(x_ref, o_ref, *, act_type, slope):
    xf = x_ref[...].astype(jnp.float32)
    o_ref[...] = _act_apply(xf, act_type, slope).astype(o_ref.dtype)


def mxnet_tpu_act_bwd(g_ref, out_ref, dx_ref, *, act_type, slope):
    gf = g_ref[...].astype(jnp.float32)
    out = out_ref[...].astype(jnp.float32)
    dx_ref[...] = (gf * _act_grad_from_out(out, act_type, slope)) \
        .astype(dx_ref.dtype)


def _flat2d(x):
    n = x.size
    cols = 128 if n >= 128 else n
    rows = _cdiv(n, cols)
    pad = rows * cols - n
    flat = x.reshape(-1)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, cols), n


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _act_kernel_core(x2, act_type, slope, interpret):
    kern = functools.partial(mxnet_tpu_act_fwd, act_type=act_type,
                             slope=slope)
    return _rows_call(kern, [(x2.shape[1], x2.dtype)], interpret, x2)


def _act_kernel_fwd(x2, act_type, slope, interpret):
    out = _act_kernel_core(x2, act_type, slope, interpret)
    return out, out          # residual = output ONLY (no input)


def _act_kernel_bwd(act_type, slope, interpret, out, g):
    kern = functools.partial(mxnet_tpu_act_bwd, act_type=act_type,
                             slope=slope)
    return (_rows_call(kern, [(out.shape[1], out.dtype)], interpret,
                       g, out),)


_act_kernel_core.defvjp(_act_kernel_fwd, _act_kernel_bwd)


def fused_act(x, act_type, slope=0.0):
    """Activation with the save-output backward as a Pallas kernel —
    the kernelized twin of ``ops/nn.py`` ``_act_core`` (same forward
    expressions, same output-only residual)."""
    from . import interpret_mode
    x2, n = _flat2d(x)
    x2p, r = _pad_rows(x2, _row_block(*x2.shape))
    out = _act_kernel_core(x2p, act_type, float(slope),
                           interpret_mode())[:r]
    return out.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# residual add + activation (the ResNet v1 block join)
# ---------------------------------------------------------------------------


def mxnet_tpu_add_act_fwd(x_ref, y_ref, o_ref, *, act_type, slope):
    s = x_ref[...].astype(jnp.float32) + y_ref[...].astype(jnp.float32)
    o_ref[...] = _act_apply(s, act_type, slope).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _add_act_core(x2, y2, act_type, slope, interpret):
    kern = functools.partial(mxnet_tpu_add_act_fwd, act_type=act_type,
                             slope=slope)
    return _rows_call(kern, [(x2.shape[1], x2.dtype)], interpret,
                      x2, y2)


def _add_act_fwd(x2, y2, act_type, slope, interpret):
    out = _add_act_core(x2, y2, act_type, slope, interpret)
    return out, out          # both addends' grads derive from out

def _add_act_bwd(act_type, slope, interpret, out, g):
    kern = functools.partial(mxnet_tpu_act_bwd, act_type=act_type,
                             slope=slope)
    dx = _rows_call(kern, [(out.shape[1], out.dtype)], interpret,
                    g, out)
    return dx, dx


_add_act_core.defvjp(_add_act_fwd, _add_act_bwd)


def fused_add_act(x, y, act_type='relu', slope=0.0):
    """``act(x + y)`` in one VMEM pass (residual-add epilogue). The
    backward reuses the save-output rule: d/dx = d/dy = g * act'(out).
    """
    from . import interpret_mode
    x2, n = _flat2d(x)
    y2, _ = _flat2d(y)
    br = _row_block(*x2.shape)
    x2p, r = _pad_rows(x2, br)
    y2p, _ = _pad_rows(y2, br)
    out = _add_act_core(x2p, y2p, act_type, float(slope),
                        interpret_mode())[:r]
    return out.reshape(-1)[:n].reshape(x.shape)
