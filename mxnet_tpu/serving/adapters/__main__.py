"""Self-test for the multi-adapter serving subsystem.

``python -m mxnet_tpu.serving.adapters`` freezes a tiny
TransformerLM once, stamps a directory of random-but-deterministic
LoRA artifacts, and drives the whole adapter path end to end on the
CPU backend.  Every leg prints one line; the verdict JSON lands in
``--out`` (default ``ADAPTERS_SELFTEST.json``) and the exit code is
0 only when every leg passes — ``tools/ci.py`` runs this as the
``adapters`` stage.

Legs:

  1 artifact         save/load roundtrip is bit-exact and digest-
                     stable; a byte flipped in params.npz or the
                     manifest is a ValueError, not a quiet wrong
                     fine-tune; a non-adapter directory is rejected.
  2 pool             row 0 is the reserved all-zero base; loading the
                     same digest twice dedups to one row; release
                     drops the pin but keeps the row warm; filling
                     the pool evicts the LRU unpinned row; with every
                     row pinned the next load raises the typed
                     AdapterExhaustedError (a BackpressureError).
  3 zero_retrace     after warmup, >= 8 distinct adapters rotate
                     through mixed greedy/sampled paged + speculative
                     traffic with the target AND draft trace_counts
                     unchanged: switching adapters is an int32 array
                     arg, never a recompile.
  4 temp0_identity   the program at temperature 0 emits the greedy
                     continuation of the uncached whole-sequence
                     forward, token for token (greedy is the
                     degenerate case, not a different code path).
  5 sampled_spec     same seed, same prompt: speculative decoding and
                     plain decoding emit the identical sampled stream
                     (coupled rejection sampling preserves the target
                     distribution token-for-token).
  6 prefix_isolation adapter ids namespace the prefix cache: a chain
                     registered under one adapter id is invisible to
                     lookups under another, and serving the same
                     prompt under two adapters never cross-reuses KV.

Usage:
  JAX_PLATFORMS=cpu python -m mxnet_tpu.serving.adapters \
      --out ADAPTERS_SELFTEST.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

from ... import config as _config  # noqa: E402
_config.cpu_rig('serving.adapters')

import numpy as onp  # noqa: E402

VOCAB = 61
PROMPT = [3, 5, 7, 11, 13]


def _model():
    from ..decode.model import init_transformer_lm
    return init_transformer_lm(VOCAB, units=32, hidden=64, layers=2,
                               heads=4, max_len=96, seed=0)


def _stamp_adapters(root, model, n, rank=4):
    from . import init_adapter, save_adapter
    paths = []
    for i in range(n):
        ad = init_adapter(model, rank=rank, seed=100 + i, scale=50.0,
                          name='ad%d' % i)
        paths.append(save_adapter(os.path.join(root, 'ad%d' % i), ad))
    return paths


def check_artifact(tmp):
    from . import init_adapter, save_adapter, load_adapter
    model, _ = _model()
    ad = init_adapter(model, rank=4, seed=1, scale=2.5, name='round')
    path = save_adapter(os.path.join(tmp, 'round'), ad)
    back = load_adapter(path)
    if back.digest != ad.digest:
        return 'digest changed across save/load'
    if back.scale != ad.scale or back.rank != ad.rank:
        return 'manifest fields changed across save/load'
    for key, arr in ad.arrays.items():
        if not onp.array_equal(back.arrays[key], arr):
            return 'array %s not bit-exact after roundtrip' % key
    # rewrite the params blob with one value nudged: the manifest
    # digest is now stale, so load must reject typed
    blob = os.path.join(path, 'params.npz')
    arrays = dict(back.arrays)
    key = sorted(arrays)[0]
    arrays[key] = arrays[key].copy()
    arrays[key].flat[0] += 1.0
    onp.savez(blob, **arrays)
    try:
        load_adapter(path)
        return 'tampered params.npz loaded without complaint'
    except ValueError:
        pass
    # tamper the manifest (scale=2.5 -> 9.5) on a fresh copy
    path2 = save_adapter(os.path.join(tmp, 'round2'), ad)
    man = os.path.join(path2, 'MANIFEST.json')
    with open(man) as f:
        doc = f.read()
    with open(man, 'w') as f:
        f.write(doc.replace('2.5', '9.5'))
    try:
        load_adapter(path2)
        return 'tampered manifest loaded without complaint'
    except ValueError:
        pass
    # a directory that is not an adapter artifact
    bogus = os.path.join(tmp, 'bogus')
    os.makedirs(bogus)
    with open(os.path.join(bogus, 'MANIFEST.json'), 'w') as f:
        json.dump({'schema': 'mxnet_tpu.frozen.v1'}, f)
    try:
        load_adapter(bogus)
        return 'non-adapter artifact loaded without complaint'
    except ValueError:
        pass
    return None


def check_pool():
    from . import (init_adapter, AdapterPool, AdapterSpec,
                   AdapterExhaustedError, BackpressureError)
    model, _ = _model()
    spec = AdapterSpec.for_model(model, rank=4, capacity=3)
    pool = AdapterPool(spec)
    st = pool.stats()
    if st['resident'] != 0 or st['capacity'] != 3:
        return 'fresh pool stats wrong: %r' % (st,)
    ads = [init_adapter(model, rank=4, seed=10 + i, name='p%d' % i)
           for i in range(3)]
    i0 = pool.load(ads[0])
    if i0 == 0:
        return 'user adapter landed on the reserved base row 0'
    if pool.load(ads[0]) != i0:
        return 'same digest loaded twice occupied two rows'
    if pool.stats()['resident'] != 1:
        return 'dedup did not dedup: %r' % (pool.stats(),)
    pool.release(i0)  # from the double load; still pinned once
    i1 = pool.load(ads[1])
    # pool full (base + 2 user rows); drop the pin on ads[0] so the
    # next load must LRU-evict that row, not error
    pool.release(i0)
    i2 = pool.load(ads[2])
    if i2 != i0:
        return 'LRU eviction did not reuse the unpinned row'
    if pool.index_of(ads[0].digest) is not None:
        return 'evicted adapter still resolvable by digest'
    # both user rows pinned now -> typed exhaustion
    try:
        pool.load(ads[0])
        return 'pinned-full pool accepted another adapter'
    except AdapterExhaustedError as exc:
        if not isinstance(exc, BackpressureError):
            return 'AdapterExhaustedError is not a BackpressureError'
    pool.release(i1)
    pool.release(i2)
    if pool.load(ads[0]) not in (i1, i2):
        return 'released rows not reused after unpin'
    return None


def check_zero_retrace(tmp):
    from ..decode.program import freeze_decode
    from ..decode.engine import DecodeEngine
    model, params = _model()
    n_adapters = 8
    root = os.path.join(tmp, 'fleet')
    _stamp_adapters(root, model, n_adapters)
    paged = freeze_decode(model, params, slots=4,
                          prefill_buckets=(16, 32), paged=True,
                          page_size=8, pages=96, spec_k=3,
                          adapter_rank=4,
                          adapter_slots=n_adapters + 1)
    from ..decode.model import init_transformer_lm
    dm, dp = init_transformer_lm(VOCAB, units=16, hidden=32, layers=1,
                                 heads=2, max_len=96, seed=9)
    draft = freeze_decode(dm, dp, slots=4, prefill_buckets=(16, 32),
                          paged=False)
    with DecodeEngine(paged, draft=draft, adapters=root,
                      name='retrace') as eng:
        # warmup: greedy, sampled and adapter-carrying streams
        list(eng.generate(PROMPT, max_new_tokens=6))
        list(eng.generate(PROMPT, max_new_tokens=6, temperature=0.7,
                          seed=1))
        list(eng.generate(PROMPT, max_new_tokens=6, adapter='ad0'))
        tc0 = dict(paged.trace_counts)
        dtc0 = dict(draft.trace_counts)
        for i in range(2 * n_adapters):
            list(eng.generate([2 + i, 9, 4, 8], max_new_tokens=8,
                              adapter='ad%d' % (i % n_adapters),
                              temperature=0.5 if i % 2 else 0.0,
                              seed=i))
        if dict(paged.trace_counts) != tc0:
            return ('adapter/sampling rotation retraced the target: '
                    '%r -> %r' % (tc0, dict(paged.trace_counts)))
        if dict(draft.trace_counts) != dtc0:
            return 'adapter/sampling rotation retraced the draft'
        st = eng.stats()
        if st['adapters']['resident'] != n_adapters:
            return ('%d adapters served but only %d resident'
                    % (n_adapters, st['adapters']['resident']))
    return None


def check_temp0_identity(tmp):
    from ..decode.program import freeze_decode
    from ..decode.engine import DecodeEngine
    model, params = _model()
    root = os.path.join(tmp, 'temp0')
    _stamp_adapters(root, model, 1)
    extras = freeze_decode(model, params, slots=4,
                           prefill_buckets=(16, 32), paged=False,
                           adapter_rank=4, adapter_slots=4)
    # the greedy continuation by the uncached whole-sequence forward
    ref = list(PROMPT)
    for _ in range(10):
        logits = onp.asarray(model.full_forward(
            params, onp.asarray([ref], 'int32')))[0]
        ref.append(int(logits[-1].argmax()))
    ref = ref[len(PROMPT):]
    with DecodeEngine(extras, adapters=root, name='t0-ext') as e2:
        got = list(e2.generate(PROMPT, max_new_tokens=10))
        base = list(e2.generate(PROMPT, max_new_tokens=10,
                                adapter='base'))
    if got != ref:
        return ('temperature-0 stream differs from the reference\'s '
                'greedy continuation: %r vs %r' % (got, ref))
    if base != ref:
        return 'adapter="base" is not bit-identical to no adapter'
    return None


def check_sampled_spec(tmp):
    from ..decode.program import freeze_decode
    from ..decode.engine import DecodeEngine
    from ..decode.model import init_transformer_lm
    model, params = _model()
    root = os.path.join(tmp, 'spec')
    _stamp_adapters(root, model, 2)
    paged = freeze_decode(model, params, slots=4,
                          prefill_buckets=(16, 32), paged=True,
                          page_size=8, pages=64, spec_k=3,
                          adapter_rank=4,
                          adapter_slots=4)
    dm, dp = init_transformer_lm(VOCAB, units=16, hidden=32, layers=1,
                                 heads=2, max_len=96, seed=9)
    draft = freeze_decode(dm, dp, slots=4, prefill_buckets=(16, 32),
                          paged=False)
    with DecodeEngine(paged, draft=draft, adapters=root,
                      name='spec') as spec_eng, \
            DecodeEngine(paged, adapters=root,
                         name='plain') as plain_eng:
        for i in range(4):
            kw = dict(max_new_tokens=12, temperature=0.9, top_p=0.85,
                      seed=77 + i)
            if i % 2:
                kw['adapter'] = 'ad%d' % (i % 2)
            a = list(spec_eng.generate([5, 6, 7], **kw))
            b = list(plain_eng.generate([5, 6, 7], **kw))
            if a != b:
                return ('seed %d: speculative %r != plain %r'
                        % (77 + i, a, b))
        st = spec_eng.stats()
        if not st['spec'].get('accepted'):
            return 'speculative path never accepted a draft token'
    return None


def check_prefix_isolation(tmp):
    import threading
    from ..decode.paged import PagedCacheSpec, PageOwner
    from ..decode.program import freeze_decode
    from ..decode.engine import DecodeEngine
    # unit level: chains registered under one namespace are invisible
    # to every other namespace
    owner = PageOwner(PagedCacheSpec({'k': ((4,), 'float32')}, 4, 64),
                      {'full': 16}, threading.Lock(), True, {})
    chain = list(range(12))
    owner.register(chain, owner.place(owner.open(0), 12),
                   namespace='ad0')
    covered, _ = owner.share_prefix(owner.open(1), chain,
                                    namespace='ad1')
    if covered:
        return ('namespace ad1 saw %d tokens of an ad0 chain'
                % covered)
    covered, _ = owner.share_prefix(owner.open(2), chain,
                                    namespace='ad0')
    if covered != 11:           # all of it but the token to step on
        return 'owning namespace lost its own chain'
    covered, _ = owner.share_prefix(owner.open(3), chain)
    if covered:
        return 'null namespace saw a namespaced chain'
    # engine level: the same prompt under two adapters yields each
    # adapter's own stream, and base traffic after adapter traffic
    # still matches a cold base engine (no KV bleed through the cache)
    model, params = _model()
    root = os.path.join(tmp, 'iso')
    _stamp_adapters(root, model, 2)
    # long enough to span full pages, so the cache has chains to hit
    prompt = [(3 * i + 1) % VOCAB for i in range(20)]
    paged = freeze_decode(model, params, slots=4,
                          prefill_buckets=(16, 32), paged=True,
                          page_size=8, pages=64,
                          adapter_rank=4, adapter_slots=4)
    with DecodeEngine(paged, adapters=root, name='iso-cold') as cold:
        want_base = list(cold.generate(prompt, max_new_tokens=8))
    with DecodeEngine(paged, adapters=root, name='iso') as eng:
        a0 = list(eng.generate(prompt, max_new_tokens=8,
                               adapter='ad0'))
        a0_again = list(eng.generate(prompt, max_new_tokens=8,
                                     adapter='ad0'))
        a1 = list(eng.generate(prompt, max_new_tokens=8,
                               adapter='ad1'))
        base = list(eng.generate(prompt, max_new_tokens=8))
        st = eng.stats()
    if a0 != a0_again:
        return 'same adapter, same prompt: streams differ'
    if a0 == a1:
        return 'two different adapters produced the same stream'
    if base != want_base:
        return ('base stream after adapter traffic differs from a '
                'cold engine: %r vs %r' % (base, want_base))
    if not st['counts'].get('prefix_tokens_saved'):
        return 'prefix cache never hit inside one namespace'
    return None


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m mxnet_tpu.serving.adapters',
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument('--out', default='ADAPTERS_SELFTEST.json')
    args = p.parse_args(argv)

    checks = {}
    with tempfile.TemporaryDirectory() as tmp:
        legs = [('artifact', lambda: check_artifact(tmp)),
                ('pool', check_pool),
                ('zero_retrace', lambda: check_zero_retrace(tmp)),
                ('temp0_identity', lambda: check_temp0_identity(tmp)),
                ('sampled_spec', lambda: check_sampled_spec(tmp)),
                ('prefix_isolation',
                 lambda: check_prefix_isolation(tmp))]
        for name, fn in legs:
            try:
                problem = fn()
            except Exception as exc:
                import traceback
                traceback.print_exc()
                problem = '%s: %s' % (type(exc).__name__, exc)
            checks[name] = problem or 'ok'
            print('adapters selftest %-16s %s' % (name, checks[name]),
                  flush=True)
    ok = all(v == 'ok' for v in checks.values())
    verdict = {'ok': ok, 'checks': checks}
    try:
        from ...resilience.checkpoint import atomic_write_bytes
        atomic_write_bytes(args.out, (json.dumps(
            verdict, indent=1, sort_keys=True) + '\n').encode())
    except Exception:
        with open(args.out, 'w') as f:
            json.dump(verdict, f, indent=1, sort_keys=True)
    print('adapters selftest: %s -> %s'
          % ('OK' if ok else 'FAIL', args.out), flush=True)
    return 0 if ok else 1


if __name__ == '__main__':
    # leave through os._exit (the mxnet_tpu.dist idiom): the verdict
    # is already flushed, and interpreter teardown can race jax's
    # CPU-client destructor against lingering daemon worker threads
    # (a C++ abort that would turn a green run into exit 134)
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
