"""Pallas kernel selftest — the ``kernels`` CI stage.

Runs every kernel family — attention (flash forward/backward,
``flash_decode``, ``flash_paged_decode``), epilogue, xent, nms —
against its reference XLA math, forward AND backward, at the
documented equivalence tiers (docs/PERFORMANCE.md "Hand-written
kernels"):

  * exact (bitwise): relu/leaky/add+relu epilogues, BN-apply forward
    against the expression-identical XLA spelling, the NMS keep mask;
  * ULP tier: transcendental activations, the fused xent head (same
    math, different rounding order);
  * reduction tier: flash attention (the online-softmax reduction
    tree legitimately rounds differently than the two-pass softmax it
    replaces).

Where the kernels run depends on the platform (``interpret_mode``).
Off-TPU they run through the Pallas interpreter at small shapes. On a
``tpu`` backend they are Mosaic-compiled — never interpreted — and the
selftest adds the shapes the two full-width models hit: BERT-base
attention (S 128, 12 heads, d 64, bf16), softmax-xent over V = 30522,
ResNet-50 BatchNorm/activation epilogues at batch 128, decode
attention at units 768 / 12 heads / max_len 512 and, paged, at 32 query
heads on 8 groups of 128 bfloat16 columns, NMS at SSD-300's
8732 boxes. References are computed at ``highest`` matmul precision,
and the tiers widen to what separately compiled TPU programs can
promise (Mosaic and XLA round exp/log differently).

Also proves the decode-engine composition: cached prefill+step token
streams with flash attention ON match the knob-on whole-sequence
reference (the K_BLOCK alignment argument).

Usage: python -m mxnet_tpu.ops.pallas [--out SELFTEST.json]
"""
from __future__ import annotations

import argparse
import json
import sys


def _check(name, fn, failures, results):
    try:
        detail = fn()
        results.append({'check': name, 'ok': True,
                        'detail': detail or {}})
        print('  ok   %s %s' % (name, detail or ''), flush=True)
    except Exception as e:            # noqa: BLE001 - report, not die
        failures.append(name)
        results.append({'check': name, 'ok': False,
                        'error': '%s: %s' % (type(e).__name__, e)})
        print('  FAIL %s: %s: %s' % (name, type(e).__name__, e),
              flush=True)


def run_selftest(out=None):
    import numpy as onp
    import jax
    import jax.numpy as jnp
    from . import (flash_attention, flash_decode_attention,
                   flash_paged_decode_attention, fused_act,
                   fused_add_act, fused_bn_apply,
                   fused_softmax_xent_rows, greedy_nms_keep,
                   interpret_mode)

    compiled = not interpret_mode()
    print('pallas selftest: backend %s, kernels %s'
          % (jax.default_backend(),
             'Mosaic-compiled' if compiled else 'interpreted'),
          flush=True)
    rs = onp.random.RandomState(0)
    failures, results = [], []
    # interpreter tiers are XLA:CPU vs XLA:CPU. Compiled tiers are
    # Mosaic vs XLA:TPU: exp/log round differently, and an f32 dot at
    # default precision is one bf16 pass on the MXU — in the kernel as
    # in the XLA path it replaces — so against the highest-precision
    # reference the reduction tier is bf16's resolution. They are
    # about twice the worst error measured on a v5e (PERF.md, PR 21:
    # activations 7.6e-5, attention forward 1.1e-2, backward 2.1e-2)
    ULP, RED, RED_GRAD = (2e-4, 2e-2, 4e-2) if compiled \
        else (2e-6, 2e-5, 2e-5)
    # a bf16 output resolves 2^-8 of its magnitude whatever computed it
    BF16, BF16_GRAD = 0.02, 0.04
    f32 = jnp.float32

    def amax(a, b):
        return float(jnp.abs(jnp.asarray(a, f32)
                             - jnp.asarray(b, f32)).max())

    def randn(*shape, dtype=f32):
        return jnp.asarray(rs.randn(*shape).astype('float32')).astype(
            dtype)

    # -- flash attention -----------------------------------------------------
    def attn_ref(q, k, v, lengths, causal):
        q, k, v = (t.astype(f32) for t in (q, k, v))
        S, D = q.shape[2], q.shape[3]
        with jax.default_matmul_precision('highest'):
            s = jnp.einsum('bhqd,bhkd->bhqk', q, k) / jnp.sqrt(float(D))
            s = jnp.where((jnp.arange(S)[None, :]
                           < lengths[:, None])[:, None, None, :],
                          s, -1e9)
            if causal:
                s = jnp.where(jnp.arange(S)[:, None]
                              >= jnp.arange(S)[None, :], s, -1e9)
            return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1),
                              v)

    def check_attn(B, H, S, D, dtype=f32, causal=True):
        tol, gtol = (RED, RED_GRAD) if dtype == f32 \
            else (BF16, BF16_GRAD)
        q, k, v, w = (randn(B, H, S, D, dtype=dtype) for _ in range(4))
        lengths = jnp.asarray(rs.randint(S // 2, S + 1, (B,)), 'int32')
        w = w.astype(f32)

        def kern(q, k, v):
            return flash_attention(q, k, v, lengths=lengths,
                                   causal=causal)

        out = jax.jit(kern)(q, k, v)
        assert out.dtype == dtype, out.dtype
        err = amax(out, attn_ref(q, k, v, lengths, causal))
        assert err < tol, 'forward err %g' % err
        g1 = jax.jit(jax.grad(
            lambda *a: (kern(*a).astype(f32) * w).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(
            lambda *a: (attn_ref(*a, lengths, causal) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)
        gerr = max(amax(a, b) for a, b in zip(g1, g2))
        assert gerr < gtol, 'grad err %g' % gerr
        return {'shape': [B, H, S, D], 'dtype': jnp.dtype(dtype).name,
                'fwd_err': err, 'grad_err': gerr}

    _check('flash_attention fwd+grad vs dense softmax',
           lambda: check_attn(2, 4, 20, 8), failures, results)
    # bf16 in, f32 accumulation (AMP composition): the reference runs
    # in f32 over the SAME bf16-quantized inputs, so the check isolates
    # the kernel's accumulation from the input quantization; the
    # tolerance is the bf16 OUTPUT's resolution
    _check('flash_attention bf16 in / f32 accumulate',
           lambda: check_attn(2, 4, 20, 8, dtype=jnp.bfloat16,
                              causal=False),
           failures, results)

    # -- decode step (slot cache and page pool) ------------------------------
    def decode_ref(q, ck, cv, pos, H):
        slots, L, U = ck.shape
        D = U // H
        with jax.default_matmul_precision('highest'):
            s = jnp.einsum('shd,slhd->shl', q.reshape(slots, H, D),
                           ck.reshape(slots, L, H, D)) \
                / jnp.sqrt(float(D))
            s = jnp.where(jnp.arange(L)[None, None, :]
                          <= pos[:, None, None], s, -1e9)
            return jnp.einsum('shl,slhd->shd', jax.nn.softmax(s, -1),
                              cv.reshape(slots, L, H, D)).reshape(
                                  slots, U)

    def check_decode(slots, L, U, H, pos):
        ck, cv = randn(slots, L, U), randn(slots, L, U)
        qd = randn(slots, U)
        pos = jnp.asarray(pos, 'int32')
        ctx = jax.jit(lambda q, k, v: flash_decode_attention(
            q, k, v, pos, heads=H))(qd, ck, cv)
        err = amax(ctx, decode_ref(qd, ck, cv, pos, H))
        assert err < RED, 'decode err %g' % err
        return {'shape': [slots, L, U], 'heads': H, 'err': err}

    def check_paged_decode(slots, L, U, H, ps, pos, dtype=f32, groups=None):
        """The walk against gather + dense softmax; ``groups`` column
        groups of the pool serve ``H`` query heads (``H`` of them, one
        each, where not given)."""
        groups = groups or H
        D = U // H
        W = groups * D
        pages = slots * (L // ps) + 1
        kp = randn(pages, ps, W, dtype=dtype)
        vp = randn(pages, ps, W, dtype=dtype)
        qd = randn(slots, U, dtype=dtype)
        pos = jnp.asarray(pos, 'int32')
        tables = jnp.asarray(1 + rs.permutation(pages - 1).reshape(
            slots, L // ps), 'int32')
        ctx = jax.jit(lambda q, k, v: flash_paged_decode_attention(
            q, k, v, tables, pos, heads=H))(qd, kp, vp)
        assert ctx.dtype == f32, ctx.dtype
        # every query head beside its own group's columns
        rep = H // groups
        ck = jnp.repeat(jnp.take(kp, tables, axis=0).reshape(
            slots, L, groups, 1, D), rep, 3).reshape(slots, L, U)
        cv = jnp.repeat(jnp.take(vp, tables, axis=0).reshape(
            slots, L, groups, 1, D), rep, 3).reshape(slots, L, U)
        err = amax(ctx, decode_ref(qd.astype(f32), ck.astype(f32),
                                   cv.astype(f32), pos, H))
        tol = RED if dtype == f32 else BF16
        assert err < tol, 'paged decode err %g' % err
        return {'pool': [pages, ps, W], 'heads': H, 'groups': groups,
                'dtype': jnp.dtype(dtype).name, 'err': err}

    def check_latent_walk(slots, L, ps, pos, heads=32, width=640,
                          values=512, used=576):
        """The walk's latent geometry (one pool; every head scores the
        whole row and reads its leading ``values`` columns) over
        consecutive tables, every chunk one copy, and over the same
        rows laid out shuffled, every page a copy of its own: one
        result bit for bit, and gather + dense softmax to rounding."""
        bf16 = jnp.bfloat16
        per = L // ps
        pages = slots * per + 1
        keep = (jnp.arange(width) < used).astype(f32)
        pool = (randn(pages, ps, width) * keep).astype(bf16)
        q = (0.2 * randn(slots, heads, width) * keep).astype(bf16)
        pos = jnp.asarray(pos, 'int32')
        perm = 1 + rs.permutation(pages - 1)
        tables = {
            'consecutive': (pool, 1 + onp.arange(pages - 1)),
            'shuffled': (pool.at[perm].set(pool[1:]), perm)}
        walk = jax.jit(lambda q, pool, t: flash_paged_decode_attention(
            q.reshape(slots, -1), pool, None, t, pos, heads=heads,
            scale=1.0, value_cols=values))
        got = {name: walk(q, pool, jnp.asarray(
            ids.reshape(slots, per), 'int32'))
            for name, (pool, ids) in tables.items()}
        assert bool(jnp.array_equal(got['consecutive'], got['shuffled'])), \
            'where the pages lie changed the result'
        rows = pool[1:].reshape(slots, L, width).astype(f32)
        seen = jnp.arange(L)[None] <= pos[:, None]
        with jax.default_matmul_precision('highest'):
            sc = jnp.einsum('shw,slw->shl', q.astype(f32), rows)
            att = jax.nn.softmax(jnp.where(seen[:, None], sc, -1e9), -1)
            want = jnp.einsum('shl,slc->shc', att, rows[..., :values])
        err = amax(got['consecutive'].reshape(slots, heads, values), want)
        assert err < BF16, 'latent walk err %g' % err
        return {'pool': [pages, ps, width], 'heads': heads,
                'values': values, 'err': err, 'bitwise': True}

    _check('flash_decode_attention vs dense softmax',
           lambda: check_decode(3, 40, 32, 4, [5, 0, 39]),
           failures, results)
    _check('flash_paged_decode_attention vs dense softmax',
           lambda: check_paged_decode(3, 48, 128, 4, 8, [5, 0, 47]),
           failures, results)
    _check('flash_paged_decode_attention, grouped queries bf16',
           lambda: check_paged_decode(3, 64, 512, 4, 16, [5, 0, 63],
                                      dtype=jnp.bfloat16, groups=2),
           failures, results)

    _check('flash_paged_decode_attention, latent rows, runs == pages',
           lambda: check_latent_walk(3, 1280, 16, [1279, 0, 700]),
           failures, results)

    def check_decode_token_streams():
        from ... import config as _config
        from ...serving.decode.model import init_transformer_lm
        # restore the caller's resolved knob value, not the bare
        # environment — library code may run the selftest mid-session
        prev = _config.get('MXNET_TPU_PALLAS')
        try:
            _config.set('MXNET_TPU_PALLAS', 'attention')
            model, params = init_transformer_lm(
                vocab=17, units=16, hidden=24, layers=2, heads=4,
                max_len=160)       # > K_BLOCK: exercises block walk
            dev = {kk: jnp.asarray(vv) for kk, vv in params.items()}
            prompt = [3, 7, 1]
            # reference: re-run the whole sequence after every token
            toks = list(prompt)
            ref = []
            for _ in range(5):
                full = model.full_forward(
                    dev, jnp.asarray([toks], 'int32'))
                t = int(jnp.argmax(full[0, -1]))
                ref.append(t)
                toks.append(t)
            # cached: prefill + steps through the slot cache
            from ...serving.decode.cache import init_cache
            cache = init_cache(model.cache_spec(), 1)
            cache, logits = model.prefill(
                dev, cache, jnp.asarray([prompt], 'int32'),
                jnp.asarray(len(prompt), 'int32'),
                jnp.asarray(0, 'int32'))
            got = [int(jnp.argmax(logits))]
            pos = len(prompt)
            while len(got) < 5:
                cache, logits = model.step(
                    dev, cache, jnp.asarray([got[-1]], 'int32'),
                    jnp.asarray([pos], 'int32'))
                got.append(int(jnp.argmax(logits[0])))
                pos += 1
            assert got == ref, 'token streams differ: %r vs %r' \
                % (got, ref)
            return {'tokens': got}
        finally:
            _config.set('MXNET_TPU_PALLAS', prev)
    _check('decode token-stream identity (flash on)',
           check_decode_token_streams, failures, results)

    # -- epilogues -----------------------------------------------------------
    def check_bn(shape, axis=1, dtype=f32):
        x = randn(*shape, dtype=dtype)
        C = shape[axis]
        g = jnp.asarray((rs.rand(C) + 0.5).astype('float32'))
        beta, mean = randn(C), randn(C)
        var = jnp.asarray((rs.rand(C) + 0.1).astype('float32'))
        scale = jax.lax.rsqrt(var + 1e-3) * g
        sh = [1] * len(shape)
        sh[axis] = -1

        def kern(x):
            return fused_bn_apply(x, scale, mean, beta, axis=axis,
                                  act_type='relu')

        def ref(x):
            return jax.nn.relu(
                (x.astype(f32) - mean.reshape(sh)) * scale.reshape(sh)
                + beta.reshape(sh)).astype(dtype)

        # expression-identical to the XLA spelling; XLA's freedom to
        # FMA-fuse mul+add differently across two separately compiled
        # programs bounds this at one ULP, not zero
        tol = ULP if dtype == f32 else 0.0
        err = amax(jax.jit(kern)(x), jax.jit(ref)(x))
        assert err <= tol, 'bn apply fwd: %g' % err
        ga = jax.jit(jax.grad(lambda x: kern(x).astype(f32).sum()))(x)
        gb = jax.jit(jax.grad(lambda x: ref(x).astype(f32).sum()))(x)
        gerr = amax(ga, gb)
        assert gerr <= tol, 'bn apply grad: %g' % gerr
        return {'shape': list(shape), 'dtype': jnp.dtype(dtype).name,
                'fwd_err': err, 'grad_err': gerr}
    _check('fused_bn_apply fwd+grad vs XLA spelling',
           lambda: check_bn((4, 6, 5, 7)), failures, results)

    def check_acts():
        x = randn(5, 33)
        refs = {'relu': jax.nn.relu, 'sigmoid': jax.nn.sigmoid,
                'tanh': jnp.tanh, 'softrelu': jax.nn.softplus,
                'softsign': jax.nn.soft_sign}
        worst = 0.0
        for act, ref in refs.items():
            err = amax(fused_act(x, act), ref(x))
            gerr = amax(
                jax.grad(lambda x: fused_act(x, act).sum())(x),
                jax.grad(lambda x: ref(x).sum())(x))
            tol = 0.0 if act == 'relu' else ULP
            assert err <= tol and gerr <= ULP, \
                '%s err %g grad %g' % (act, err, gerr)
            worst = max(worst, err, gerr)
        return {'worst_err': worst}
    _check('fused_act family fwd+grad', check_acts, failures, results)

    def check_add_relu(shape, dtype=f32):
        x, y = randn(*shape, dtype=dtype), randn(*shape, dtype=dtype)
        err = amax(jax.jit(fused_add_act)(x, y), jax.nn.relu(x + y))
        gx, gy = jax.jit(jax.grad(
            lambda x, y: fused_add_act(x, y).astype(f32).sum(),
            argnums=(0, 1)))(x, y)
        gr = jax.grad(lambda x, y: jax.nn.relu(x + y).astype(f32)
                      .sum())(x, y)
        assert err == 0.0 and amax(gx, gr) == 0.0 \
            and amax(gy, gr) == 0.0
        return {'shape': list(shape), 'tier': 'exact'}
    _check('fused_add_act bitwise vs relu(x+y)',
           lambda: check_add_relu((5, 33)), failures, results)

    # -- fused xent ----------------------------------------------------------
    def check_xent(rows, V, dtype=f32):
        logits = (randn(rows, V) * 3).astype(dtype)
        labels = jnp.asarray(rs.randint(0, V, (rows,)))

        def ref(x):
            return -jnp.take_along_axis(
                jax.nn.log_softmax(x.astype(f32), -1),
                labels[:, None], axis=-1)[:, 0]

        nll = jax.jit(fused_softmax_xent_rows)(logits, labels)
        assert nll.dtype == f32, nll.dtype            # f32 loss
        err = amax(nll, jax.jit(ref)(logits))
        assert err < ULP, 'xent fwd %g' % err
        gg = jax.jit(jax.grad(lambda x: fused_softmax_xent_rows(
            x, labels).sum()))(logits)
        assert gg.dtype == dtype, gg.dtype            # primal dtype
        gerr = amax(gg, jax.jit(jax.grad(
            lambda x: ref(x).sum()))(logits))
        # a bf16 gradient is exact only to bf16's resolution
        assert gerr < (ULP if dtype == f32 else 4e-3), \
            'xent grad %g' % gerr
        return {'shape': [rows, V], 'dtype': jnp.dtype(dtype).name,
                'fwd_err': err, 'grad_err': gerr}
    _check('fused_softmax_xent fwd+grad vs log_softmax+pick',
           lambda: check_xent(7, 33), failures, results)
    _check('fused_softmax_xent bf16 logits / f32 loss',
           lambda: check_xent(5, 21, dtype=jnp.bfloat16),
           failures, results)

    # -- nms -------------------------------------------------------------------
    def check_nms(N, topk):
        xy = rs.rand(N, 2).astype('float32') * 300
        wh = rs.rand(N, 2).astype('float32') * 60 + 4
        b = onp.concatenate([xy, xy + wh], 1)
        keep = onp.asarray(greedy_nms_keep(
            jnp.asarray(b), jnp.ones((N,), bool), 0.45, topk=topk))
        want = onp.ones(N, bool)
        area = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
        for i in range(min(topk, N)):
            if not want[i]:
                continue
            iw = onp.minimum(b[:, 2], b[i, 2]) \
                - onp.maximum(b[:, 0], b[i, 0])
            ih = onp.minimum(b[:, 3], b[i, 3]) \
                - onp.maximum(b[:, 1], b[i, 1])
            inter = onp.maximum(iw, 0) * onp.maximum(ih, 0)
            iou = inter / (area + area[i] - inter + 1e-12)
            want[(iou > 0.45) & (onp.arange(N) > i)] = False
        # a box whose IoU sits within rounding of the threshold may
        # legitimately flip between the two float orders
        flips = int((keep != want).sum())
        assert flips <= N // 1000, '%d keep-mask mismatches' % flips
        return {'boxes': N, 'topk': topk, 'kept': int(keep.sum()),
                'mismatch': flips}
    _check('greedy_nms_keep vs numpy greedy NMS',
           lambda: check_nms(200, 200), failures, results)

    # -- the shapes the two full-width models hit (Mosaic only: the
    # interpreter would take minutes per case and proves nothing new) ---------
    if compiled:
        bf16 = jnp.bfloat16
        _check('full width: flash_attention BERT-base bf16',
               lambda: check_attn(96, 12, 128, 64, dtype=bf16,
                                  causal=False),
               failures, results)
        _check('full width: flash_attention causal f32',
               lambda: check_attn(8, 12, 128, 64), failures, results)
        pos = [0, 5, 17, 100, 127, 128, 300, 511]
        _check('full width: flash_decode_attention 768/12/512',
               lambda: check_decode(8, 512, 768, 12, pos),
               failures, results)
        _check('full width: flash_paged_decode_attention 768/12/512',
               lambda: check_paged_decode(8, 512, 768, 12, 16, pos),
               failures, results)
        _check('full width: flash_paged_decode_attention 32 on 8 x 128 '
               'bf16',
               lambda: check_paged_decode(8, 512, 4096, 32, 16, pos,
                                          dtype=bf16, groups=8),
               failures, results)
        _check('full width: flash_paged_decode_attention latent 32 x 640 '
               'bf16, consecutive and shuffled',
               lambda: check_latent_walk(
                   8, 16384, 16,
                   [16383, 0, 8200, 1023, 1024, 12345, 16, 4095]),
               failures, results)
        for dt in (bf16, f32):
            _check('full width: fused_softmax_xent V=30522 %s'
                   % jnp.dtype(dt).name,
                   lambda dt=dt: check_xent(96 * 20, 30522, dtype=dt),
                   failures, results)
        # ResNet-50 BatchNorm+relu sites at batch 128, both layouts
        for shape, axis in [((128, 64, 112, 112), 1),
                            ((128, 256, 56, 56), 1),
                            ((128, 512, 28, 28), 1),
                            ((128, 1024, 14, 14), 1),
                            ((128, 2048, 7, 7), 1),
                            ((128, 112, 112, 64), 3),
                            ((128, 56, 56, 256), 3),
                            ((128, 7, 7, 2048), 3)]:
            _check('full width: fused_bn_apply+relu %s axis %d bf16'
                   % (shape, axis),
                   lambda s=shape, a=axis: check_bn(s, a, dtype=bf16),
                   failures, results)
        _check('full width: fused_add_act (128, 256, 56, 56) bf16',
               lambda: check_add_relu((128, 256, 56, 56), dtype=bf16),
               failures, results)
        _check('full width: greedy_nms_keep SSD-300',
               lambda: check_nms(8732, 400), failures, results)

    status = 'ok' if not failures else 'fail'
    payload = {'schema': 'mxnet_tpu.pallas_selftest.v1',
               'status': status, 'failures': failures,
               'backend': jax.default_backend(),
               'kernels': 'mosaic' if compiled else 'interpreter',
               'checks': results}
    if out:
        with open(out, 'w') as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write('\n')
        print('pallas selftest: wrote %s' % out)
    print('pallas selftest: %s (%d checks, %d failed)'
          % (status, len(results), len(failures)))
    return payload


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m mxnet_tpu.ops.pallas',
        description=__doc__.split('\n\n')[0])
    p.add_argument('--out', default=None,
                   help='selftest artifact path (JSON)')
    args = p.parse_args(argv)
    payload = run_selftest(out=args.out)
    return 0 if payload['status'] == 'ok' else 1


if __name__ == '__main__':
    sys.exit(main())
