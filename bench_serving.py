#!/usr/bin/env python
"""Serving bench: closed-loop latency/throughput sweep over the
bucket ladder, plus the autoregressive generation sweep
(docs/SERVING.md; CI stages 'bench-serving' and 'bench-decode').

Default mode — one-shot inference, for every batch bucket:

  * closed-loop single requests through the micro-batcher (one
    in-flight request per client, ``--clients`` concurrent clients)
    — measures request latency under batching: p50/p99, requests/s;
  * bulk batches of exactly the bucket size through the AOT program
    (``InferenceSession.infer_batch``) — measures the compiled
    program's examples/s ceiling per bucket.

``--decode`` mode — generation, a mixed-length workload (varying
prompt lengths AND generation budgets) decoded two ways over the SAME
frozen decode program:

  * **continuous batching** (the decode engine): sequences join/leave
    the slot register file at token granularity;
  * **flush batching** (the baseline the engine replaces): groups of
    ``slots`` sequences prefill together and the whole group holds
    its slots until the LONGEST member finishes.

Both report tokens/s, time-to-first-token p50/p99 and per-token
latency p50/p99; the payload records the continuous/flush ratios and
a per-request token-stream cross-check (same greedy model, so any
mismatch is an engine bug, not noise).

Writes the standard instrument status JSON (mxnet_tpu.instrument.v2:
``status`` ok|degraded|unavailable, rc 0 on outage — the
contract every instrument in this repo honors) with
the telemetry summary block.

Usage: python bench_serving.py [--quick] [--decode]
                               [--out BENCH_SERVING.json]
"""
import argparse
import os
import sys
import threading
import time

sys.path.insert(0, '.')
import numpy as np  # noqa: E402

FEATURES = 64
CLASSES = 16


def _build_frozen(max_batch):
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    np.random.seed(5)
    mx.random.seed(5)
    data = mx.sym.Variable('data')
    h = mx.sym.FullyConnected(data, num_hidden=128, name='fc1')
    h = mx.sym.Activation(h, act_type='relu')
    h = mx.sym.FullyConnected(h, num_hidden=128, name='fc2')
    h = mx.sym.Activation(h, act_type='relu')
    h = mx.sym.FullyConnected(h, num_hidden=CLASSES, name='fc3')
    out = mx.sym.SoftmaxOutput(h, name='softmax')
    mod = mx.mod.Module(out, context=mx.context.current_context())
    rs = np.random.RandomState(0)
    x = rs.randn(64, FEATURES).astype('float32')
    y = rs.randint(0, CLASSES, (64,)).astype('float32')
    it = mx.io.NDArrayIter(x, y, batch_size=32)
    mod.fit(it, num_epoch=1, optimizer_params=(('learning_rate', 0.1),))
    return serving.freeze(mod, max_batch=max_batch,
                          name='bench-serving')


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def bench_bucket(session, bucket, seconds, clients):
    """Closed-loop clients + bulk-batch throughput for one bucket."""
    rs = np.random.RandomState(bucket)
    x1 = rs.randn(FEATURES).astype('float32')
    xb = rs.randn(bucket, FEATURES).astype('float32')
    session.infer_batch([xb])          # compile outside the window

    latencies = []
    lock = threading.Lock()
    stop = time.perf_counter() + seconds

    def client():
        mine = []
        while time.perf_counter() < stop:
            t0 = time.perf_counter()
            session.infer(x1, timeout=30)
            mine.append(time.perf_counter() - t0)
        with lock:
            latencies.extend(mine)

    threads = [threading.Thread(target=client)
               for _ in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + 30)
    wall = time.perf_counter() - t_start

    # bulk path: examples/s of the padded compiled program
    reps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        session.infer_batch([xb])
        reps += 1
    bulk_dt = time.perf_counter() - t0

    latencies.sort()
    return {
        'bucket': bucket,
        'requests': len(latencies),
        'requests_per_sec': round(len(latencies) / wall, 2)
        if wall else None,
        'latency_p50_ms': round(1e3 * _percentile(latencies, 0.50), 3)
        if latencies else None,
        'latency_p99_ms': round(1e3 * _percentile(latencies, 0.99), 3)
        if latencies else None,
        'bulk_examples_per_sec': round(reps * bucket / bulk_dt, 1)
        if bulk_dt else None,
    }


# ---------------------------------------------------------------------------
# generation sweep (--decode): continuous vs flush batching
# ---------------------------------------------------------------------------

def _decode_workload(quick, slots):
    """Deterministic mixed-length workload: prompts 2..16 tokens,
    generation budgets drawn from a short/long mix — the shape where
    continuous batching earns its keep."""
    rs = np.random.RandomState(17)
    n = 3 * slots if quick else 8 * slots
    budgets = [4, 6, 8, 12, 16, 24]
    return [(list(rs.randint(1, 48, rs.randint(2, 17))),
             int(budgets[rs.randint(len(budgets))]))
            for _ in range(n)]


def _gen_stats(name, wall, ttfts, token_stamps):
    """tokens/s + TTFT/per-token percentiles from per-request
    timestamp traces."""
    tpots = []
    total = 0
    for stamps in token_stamps:
        total += len(stamps)
        tpots.extend(b - a for a, b in zip(stamps, stamps[1:]))
    ttfts = sorted(ttfts)
    tpots.sort()
    ms = lambda v: None if v is None else round(1e3 * v, 3)  # noqa: E731
    return {
        'mode': name,
        'requests': len(ttfts),
        'tokens': total,
        'wall_s': round(wall, 3),
        'tokens_per_sec': round(total / wall, 1) if wall else None,
        'ttft_p50_ms': ms(_percentile(ttfts, 0.50)),
        'ttft_p99_ms': ms(_percentile(ttfts, 0.99)),
        'tpot_p50_ms': ms(_percentile(tpots, 0.50)),
        'tpot_p99_ms': ms(_percentile(tpots, 0.99)),
    }


def _bench_continuous(prog, requests):
    """All requests arrive at t0; the decode engine schedules joins
    and retirements at token granularity."""
    from mxnet_tpu import serving
    session = serving.InferenceSession(prog, watchdog=False,
                                       timeout_s=600.0)
    ttfts = [None] * len(requests)
    stamps = [None] * len(requests)
    tokens = [None] * len(requests)

    def consume(i, stream, t0):
        mine = []
        for _tok in stream:
            mine.append(time.perf_counter())
        ttfts[i] = mine[0] - t0 if mine else float('inf')
        stamps[i] = mine
        tokens[i] = list(stream.tokens)

    try:
        t0 = time.perf_counter()
        streams = [session.generate(p, max_new_tokens=n)
                   for p, n in requests]
        threads = [threading.Thread(target=consume, args=(i, s, t0))
                   for i, s in enumerate(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        wall = time.perf_counter() - t0
    finally:
        session.close()
    return _gen_stats('continuous', wall, ttfts, stamps), tokens


def _bench_flush(prog, requests):
    """Baseline: groups of ``slots`` prefill together and decode until
    the whole group finishes — finished members' rows are wasted and
    the next group waits (exactly what continuous batching removes)."""
    slots = prog.slots
    ttfts = [None] * len(requests)
    stamps = [[] for _ in requests]
    tokens = [None] * len(requests)
    cache = prog.new_cache()
    t0 = time.perf_counter()
    for base in range(0, len(requests), slots):
        group = requests[base:base + slots]
        states = []
        for i, (prompt, max_new) in enumerate(group):
            cache, tok, _ = prog.run_prefill(cache, prompt, i)
            now = time.perf_counter()
            ttfts[base + i] = now - t0
            stamps[base + i].append(now)
            states.append({'toks': [tok], 'pos': len(prompt),
                           'last': tok, 'max_new': max_new})
        while True:
            live = [i for i, s in enumerate(states)
                    if len(s['toks']) < s['max_new']
                    and s['pos'] + 1 < prog.max_len]
            if not live:
                break
            tk = np.zeros(slots, 'int32')
            ps = np.zeros(slots, 'int32')
            for i, s in enumerate(states):
                tk[i] = s['last']
                ps[i] = s['pos']
            cache, out, _ = prog.run_step(cache, tk, ps)
            now = time.perf_counter()
            for i in live:
                s = states[i]
                s['pos'] += 1
                s['last'] = int(out[i])
                s['toks'].append(s['last'])
                stamps[base + i].append(now)
        for i, s in enumerate(states):
            tokens[base + i] = s['toks']
    wall = time.perf_counter() - t0
    return _gen_stats('flush', wall, ttfts, stamps), tokens


def run_decode(status, args):
    from mxnet_tpu.serving.decode import DecodeProgram, init_rnn_lm

    slots = 4 if args.quick else 8
    model, params = init_rnn_lm(vocab=48, embed=32, hidden=64,
                                layers=1, mode='lstm', max_len=64,
                                seed=9)
    prog = DecodeProgram(model, params, slots=slots,
                         prefill_buckets=(4, 8, 16))
    prog.warmup()          # compile outside the timed windows
    requests = _decode_workload(args.quick, slots)

    flush_rec, flush_tokens = _bench_flush(prog, requests)
    cont_rec, cont_tokens = _bench_continuous(prog, requests)
    mismatches = sum(1 for a, b in zip(cont_tokens, flush_tokens)
                     if a != b)
    for rec in (flush_rec, cont_rec):
        print('%-11s %7s tok/s  ttft p50/p99 %s/%s ms  '
              'tpot p50/p99 %s/%s ms'
              % (rec['mode'], rec['tokens_per_sec'],
                 rec['ttft_p50_ms'], rec['ttft_p99_ms'],
                 rec['tpot_p50_ms'], rec['tpot_p99_ms']), flush=True)

    bound = len(prog.prefill_buckets) + 1
    speedup = (cont_rec['tokens_per_sec']
               / flush_rec['tokens_per_sec']) \
        if flush_rec['tokens_per_sec'] else None
    payload = {
        'metrics': [{
            'metric': 'decode_generation_sweep',
            'unit': 'tokens/s',
            'slots': slots,
            'requests': len(requests),
            'prefill_buckets': list(prog.prefill_buckets),
            'continuous': cont_rec,
            'flush': flush_rec,
            'tokens_per_sec_ratio': round(speedup, 3)
            if speedup else None,
            'continuous_beats_flush': bool(
                speedup and speedup > 1.0
                and cont_rec['ttft_p99_ms'] < flush_rec['ttft_p99_ms']),
            'token_stream_mismatches': mismatches,
            'recompile_count': prog.compile_count,
            'recompile_bound': bound,
            'recompiles_bounded': prog.compile_count <= bound,
        }],
    }
    try:
        from mxnet_tpu import observability
        payload['telemetry'] = observability.summary()
    except Exception as e:
        payload['telemetry'] = {'enabled': False,
                                'error': '%s: %s'
                                % (type(e).__name__, e)}
    m = payload['metrics'][0]
    if not m['recompiles_bounded']:
        raise AssertionError(
            '%d decode programs compiled; bound is prefill ladder + 1'
            ' = %d' % (prog.compile_count, bound))
    if mismatches:
        raise AssertionError(
            '%d/%d token streams differ between continuous and flush '
            'decoding (same greedy model: engine bug)'
            % (mismatches, len(requests)))
    return payload


# ---------------------------------------------------------------------------
# paged KV cache sweep (--paged): capacity at equal HBM budget,
# prefix-sharing TTFT, speculative decoding A/B
# ---------------------------------------------------------------------------

def _paged_model(quick):
    from mxnet_tpu.serving.decode import init_transformer_lm
    if quick:
        return init_transformer_lm(vocab=48, units=32, hidden=48,
                                   layers=2, heads=4, max_len=96,
                                   seed=11)
    return init_transformer_lm(vocab=96, units=64, hidden=128,
                               layers=4, heads=8, max_len=256,
                               seed=11)


def _greedy_reference(model, params, prompt, n):
    import jax.numpy as jnp
    dev = {k: jnp.asarray(v) for k, v in params.items()}
    toks = list(prompt)
    out = []
    for _ in range(n):
        full = np.asarray(model.full_forward(
            dev, jnp.asarray([toks], 'int32')))
        t = int(full[0, -1].argmax())
        out.append(t)
        toks.append(t)
    return out


def _capacity_leg(model, params, quick):
    """Max concurrent sequences at EQUAL HBM budget, slot vs paged —
    measured via the pool-bytes accounting and confirmed by actually
    admitting that many sequences into a live engine."""
    from mxnet_tpu.serving.decode import (DecodeEngine, DecodeProgram,
                                          PagedDecodeProgram)
    slot_slots = 4 if quick else 8
    page_size = 8 if quick else 16
    slot_prog = DecodeProgram(model, params, slots=slot_slots,
                              prefill_buckets=(8,))
    budget = slot_prog.cache_bytes()          # the HBM budget to match
    # workload: prompt 8 + up to 6 generated -> <= 14-token sequences
    prompt_len, gen = 8, 6
    paged_tmp = PagedDecodeProgram(model, params, slots=1,
                                   prefill_buckets=(8,),
                                   page_size=page_size)
    pages_budget = budget // paged_tmp.page_bytes()
    per_seq_pages = -(-(prompt_len + gen) // page_size)
    capacity = int(pages_budget // per_seq_pages)
    prog = PagedDecodeProgram(model, params, slots=capacity,
                              prefill_buckets=(8,),
                              page_size=page_size,
                              pages=pages_budget + 1)
    prog.warmup()
    eng = DecodeEngine(prog, timeout_s=120.0, max_queue=capacity + 4)
    rs = np.random.RandomState(23)
    try:
        streams = [eng.generate(list(rs.randint(1, 40, prompt_len)),
                                max_new_tokens=gen)
                   for _ in range(capacity)]
        peak = 0
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            st = eng.stats()
            peak = max(peak, st['active'])
            if all(s.done() for s in streams):
                break
            time.sleep(0.005)
        st = eng.stats()
        for s in streams:
            s.result(60)
    finally:
        eng.close()
    return {
        'hbm_budget_bytes': int(budget),
        'page_size': page_size,
        'slot': {'max_concurrent_sequences': slot_slots,
                 'per_sequence_bytes':
                     int(slot_prog.per_sequence_bytes())},
        'paged': {'max_concurrent_sequences': capacity,
                  'per_sequence_bytes': int(per_seq_pages
                                            * prog.page_bytes()),
                  'pool_bytes': int(prog.cache_bytes()),
                  'peak_active_measured': peak,
                  'pool_exhausted': st['counts']['pool_exhausted']},
        'concurrency_ratio': round(capacity / float(slot_slots), 3),
        'all_completed': True,
    }


def _ttft_run(model, params, requests, prefix_cache, page_size,
              max_len_bucket):
    """Drive one engine over the shared-prefix workload; returns
    sorted TTFTs + engine stats."""
    import threading as _threading
    from mxnet_tpu.serving.decode import (DecodeEngine,
                                          PagedDecodeProgram)
    prog = PagedDecodeProgram(model, params, slots=4,
                              prefill_buckets=(max_len_bucket,),
                              page_size=page_size)
    prog.warmup()
    eng = DecodeEngine(prog, timeout_s=300.0,
                       max_queue=len(requests) + 4,
                       prefix_cache=prefix_cache)
    # execute (not just compile) every program once outside the timed
    # window — a compiled executable's FIRST run carries one-time
    # setup cost that would otherwise land on whichever leg runs
    # fewer prefills
    eng.generate([43, 42, 41], max_new_tokens=2).result(120)
    ttfts = [None] * len(requests)

    def consume(i, stream, t0):
        # the iterator re-raises a failed stream's typed error; the
        # finally keeps ttfts[i] a float either way so the percentile
        # math reports the failure as inf instead of dying on None
        try:
            for _tok in stream:
                if ttfts[i] is None:
                    ttfts[i] = time.perf_counter() - t0
        except Exception:
            pass
        finally:
            if ttfts[i] is None:
                ttfts[i] = float('inf')

    try:
        t0 = time.perf_counter()
        streams = [eng.generate(p, max_new_tokens=n)
                   for p, n in requests]
        threads = [_threading.Thread(target=consume, args=(i, s, t0))
                   for i, s in enumerate(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        wall = time.perf_counter() - t0
        st = eng.stats()
    finally:
        eng.close()
    return sorted(ttfts), wall, st


def _prefix_leg(model, params, quick):
    """Shared-prefix workload (a few hot system prompts + short user
    suffixes): TTFT with prefix sharing vs without, same rig, same
    program geometry."""
    rs = np.random.RandomState(31)
    n_req = 20 if quick else 48
    sys_len = 56 if quick else 120
    bucket = 64 if quick else 128
    page_size = 8 if quick else 16
    # Zipf-distributed choice over 3 system prompts (rank-skewed: the
    # hot prompt dominates, the tail still occurs). Page-aligned
    # system prompts + one-token user suffixes + short generations
    # keep the workload prefill-dominated — the regime prefix sharing
    # targets: every no-sharing admit re-runs the whole bucket-sized
    # prefill (~6x a decode step on this rig), a hit replaces it with
    # ONE decode step riding the already-batched tick
    sys_prompts = [list(rs.randint(1, 40, sys_len)) for _ in range(3)]
    weights = np.array([1.0 / (r + 1) for r in range(3)])
    weights /= weights.sum()
    requests = []
    for _ in range(n_req):
        sp = sys_prompts[rs.choice(3, p=weights)]
        requests.append((sp + [int(rs.randint(1, 40))], 3))
    shared, wall_s, st_s = _ttft_run(model, params, requests, True,
                                     page_size, bucket)
    unshared, wall_u, st_u = _ttft_run(model, params, requests, False,
                                       page_size, bucket)
    ms = lambda v: None if v is None else round(1e3 * v, 3)  # noqa: E731
    return {
        'requests': n_req, 'system_prompt_len': sys_len,
        'zipf_system_prompts': len(sys_prompts),
        'sharing': {
            'ttft_p50_ms': ms(_percentile(shared, 0.50)),
            'ttft_p99_ms': ms(_percentile(shared, 0.99)),
            'wall_s': round(wall_s, 3),
            'prefix_hits': st_s['counts']['prefix_hits'],
            'prefix_tokens_saved':
                st_s['counts']['prefix_tokens_saved'],
            'cow_copies': st_s['counts']['cow_copies'],
        },
        'no_sharing': {
            'ttft_p50_ms': ms(_percentile(unshared, 0.50)),
            'ttft_p99_ms': ms(_percentile(unshared, 0.99)),
            'wall_s': round(wall_u, 3),
        },
        'ttft_p99_improved': (_percentile(shared, 0.99)
                              < _percentile(unshared, 0.99)),
    }


def _spec_leg(model, params, quick):
    """Speculative decoding A/B: tokens/s and acceptance rate with a
    small draft vs the plain paged engine, platform-tagged (CPU-rig
    numbers are honest: a toy draft costs a comparable step to the
    toy target, so the win only materializes at real model ratios)."""
    import jax
    from mxnet_tpu.serving.decode import (DecodeEngine, DecodeProgram,
                                          PagedDecodeProgram,
                                          init_transformer_lm)
    slots = 4
    page_size = 8 if quick else 16
    spec_k = 3
    vocab = int(model.vocab)
    dmodel, dparams = init_transformer_lm(
        vocab, units=16, hidden=16, layers=1, heads=2,
        max_len=model.max_len, seed=7)
    rs = np.random.RandomState(41)
    requests = [(list(rs.randint(1, vocab - 4, 6)), 10 if quick
                 else 24) for _ in range(2 * slots)]

    def drive(spec):
        prog = PagedDecodeProgram(model, params, slots=slots,
                                  prefill_buckets=(8,),
                                  page_size=page_size,
                                  spec_k=spec_k if spec else 0)
        prog.warmup()
        draft = None
        if spec:
            draft = DecodeProgram(dmodel, dparams, slots=slots,
                                  prefill_buckets=(8,))
            draft.warmup()
        eng = DecodeEngine(prog, timeout_s=300.0,
                           max_queue=len(requests) + 4, draft=draft)
        try:
            t0 = time.perf_counter()
            streams = [eng.generate(p, max_new_tokens=n)
                       for p, n in requests]
            outs = [s.result(300) for s in streams]
            wall = time.perf_counter() - t0
            st = eng.stats()
        finally:
            eng.close()
        tokens = sum(len(o) for o in outs)
        return {'tokens': tokens, 'wall_s': round(wall, 3),
                'tokens_per_sec': round(tokens / wall, 1)
                if wall else None}, st, outs

    plain_rec, _plain_st, plain_outs = drive(spec=False)
    spec_rec, spec_st, _spec_outs = drive(spec=True)
    return {
        'platform': jax.default_backend(),
        'spec_k': spec_k,
        'draft': 'transformer_lm-1layer-16u',
        'baseline': plain_rec,
        'speculative': dict(spec_rec,
                            acceptance_rate=spec_st['spec']
                            ['acceptance_rate'],
                            proposed=spec_st['spec']['proposed'],
                            accepted=spec_st['spec']['accepted']),
        'tokens_per_sec_ratio': round(
            spec_rec['tokens_per_sec'] / plain_rec['tokens_per_sec'],
            3) if plain_rec['tokens_per_sec'] else None,
    }, plain_outs, requests


def run_paged(status, args):
    """--paged: the decode-memory-wall sweep (docs/SERVING.md "Paged
    KV cache, prefix sharing, speculative decoding")."""
    model, params = _paged_model(args.quick)

    capacity = _capacity_leg(model, params, args.quick)
    print('capacity @ equal HBM: slot %d -> paged %d concurrent '
          '(%.1fx), pool_exhausted=%d'
          % (capacity['slot']['max_concurrent_sequences'],
             capacity['paged']['max_concurrent_sequences'],
             capacity['concurrency_ratio'],
             capacity['paged']['pool_exhausted']), flush=True)

    prefix = _prefix_leg(model, params, args.quick)
    print('prefix TTFT p99: sharing %s ms vs no-sharing %s ms '
          '(hits=%d, saved=%d tokens)'
          % (prefix['sharing']['ttft_p99_ms'],
             prefix['no_sharing']['ttft_p99_ms'],
             prefix['sharing']['prefix_hits'],
             prefix['sharing']['prefix_tokens_saved']), flush=True)

    spec, plain_outs, spec_requests = _spec_leg(model, params,
                                               args.quick)
    print('speculative: %s tok/s vs baseline %s tok/s, acceptance %s'
          % (spec['speculative']['tokens_per_sec'],
             spec['baseline']['tokens_per_sec'],
             spec['speculative']['acceptance_rate']), flush=True)

    # bit-identity proof: the non-speculative paged streams equal the
    # uncached whole-sequence reference
    mismatches = 0
    for (prompt, n), out in zip(spec_requests[:4], plain_outs[:4]):
        if out != _greedy_reference(model, params, prompt, len(out)):
            mismatches += 1
    payload = {
        'metrics': [{
            'metric': 'paged_decode_sweep',
            'unit': 'concurrent sequences / tokens/s',
            'capacity_equal_hbm': capacity,
            'prefix_sharing': prefix,
            'speculative': spec,
            'paged_bit_identity_mismatches': mismatches,
        }],
    }
    try:
        from mxnet_tpu import observability
        payload['telemetry'] = observability.summary()
    except Exception as e:
        payload['telemetry'] = {'enabled': False,
                                'error': '%s: %s'
                                % (type(e).__name__, e)}
    if mismatches:
        raise AssertionError(
            '%d non-speculative paged token streams differ from the '
            'uncached reference' % mismatches)
    if capacity['concurrency_ratio'] < 4.0:
        raise AssertionError(
            'paged capacity at equal HBM budget is %.2fx the slot '
            'cache; the acceptance bar is >= 4x'
            % capacity['concurrency_ratio'])
    if capacity['paged']['pool_exhausted']:
        raise AssertionError('accounting-derived capacity exhausted '
                             'the pool — pool-bytes accounting is '
                             'wrong')
    share_p99 = prefix['sharing']['ttft_p99_ms']
    noshare_p99 = prefix['no_sharing']['ttft_p99_ms']
    if share_p99 is not None and noshare_p99 is not None \
            and share_p99 > noshare_p99 * 1.1:
        raise AssertionError(
            'prefix sharing worsened TTFT p99 (%.1f ms vs %.1f ms '
            'no-sharing, >10%% past noise) on the prefix-heavy '
            'workload' % (share_p99, noshare_p99))
    return payload


# ---------------------------------------------------------------------------
# multi-adapter sweep (--adapters): Zipf fleet rotation at zero
# retraces, adapter-vs-base throughput A/B
# ---------------------------------------------------------------------------

def run_adapters(status, args):
    """--adapters: the multi-adapter serving sweep (docs/SERVING.md
    "Multi-adapter serving & sampling"). One paged program frozen
    with an adapter pool in its compiled signature serves a Zipf
    rotation over 8 LoRA artifacts with half the traffic sampled;
    gates zero retraces after warmup, the whole fleet resident, and
    reports the adapter-traffic throughput next to a base-only run
    of the same program (the overhead of gathering per-slot deltas
    inside the one compiled step)."""
    import tempfile
    import jax
    from mxnet_tpu.serving.adapters import (AdapterSpec, init_adapter,
                                            save_adapter)
    from mxnet_tpu.serving.decode import (DecodeEngine,
                                          PagedDecodeProgram)
    model, params = _paged_model(args.quick)
    fleet, rank, slots = 8, 4, 4
    page_size = 8 if args.quick else 16
    aspec = AdapterSpec.for_model(model, rank=rank,
                                  capacity=fleet + 1)
    prog = PagedDecodeProgram(model, params, slots=slots,
                              prefill_buckets=(8,),
                              page_size=page_size,
                              adapter_spec=aspec)
    vocab = int(model.vocab)
    rs = np.random.RandomState(17)
    requests = [(list(rs.randint(1, vocab - 4, 6)),
                 10 if args.quick else 24)
                for _ in range(4 * slots)]

    def drive(eng, use_fleet):
        t0 = time.perf_counter()
        streams = []
        for i, (prompt, n) in enumerate(requests):
            kw = {}
            if use_fleet:
                # harmonic Zipf over base + fleet, sampled every
                # other request — the loadgen adapters-mode shape
                kw['adapter'] = 'ad%d' % (i % fleet) if i % 3 else \
                    'base'
                if i % 2:
                    kw.update(temperature=0.8, top_p=0.9, seed=i)
            streams.append(eng.generate(prompt, max_new_tokens=n,
                                        **kw))
        outs = [s.result(300) for s in streams]
        wall = time.perf_counter() - t0
        tokens = sum(len(o) for o in outs)
        return {'tokens': tokens, 'wall_s': round(wall, 3),
                'tokens_per_sec': round(tokens / wall, 1)
                if wall else None}

    with tempfile.TemporaryDirectory() as root:
        for i in range(fleet):
            save_adapter(os.path.join(root, 'ad%d' % i),
                         init_adapter(model, rank=rank, seed=60 + i,
                                      scale=50.0, name='ad%d' % i))
        eng = DecodeEngine(prog, timeout_s=300.0,
                           max_queue=len(requests) + 4,
                           adapters=root)
        try:
            # warmup every compiled path (greedy/sampled x
            # base/adapter) and pre-load the fleet, then snapshot
            for kw in ({}, {'temperature': 0.8, 'seed': 1},
                       *({'adapter': 'ad%d' % i} for i in
                         range(fleet)),
                       {'adapter': 'ad0', 'temperature': 0.5,
                        'seed': 2}):
                eng.generate([1, 2, 3], max_new_tokens=4,
                             **kw).result(300)
            tc0 = dict(prog.trace_counts)
            base_rec = drive(eng, use_fleet=False)
            fleet_rec = drive(eng, use_fleet=True)
            retraced = {k: v for k, v in prog.trace_counts.items()
                        if tc0.get(k) != v}
            st = eng.stats()
        finally:
            eng.close()
    print('adapters: fleet %s tok/s vs base-only %s tok/s, '
          'resident=%d loads=%d, retraced=%s'
          % (fleet_rec['tokens_per_sec'], base_rec['tokens_per_sec'],
             st['adapters']['resident'], st['adapters']['loads'],
             retraced or 'none'), flush=True)
    payload = {
        'metrics': [{
            'metric': 'multi_adapter_sweep',
            'unit': 'tokens/s',
            'platform': jax.default_backend(),
            'adapter_fleet': fleet,
            'adapter_rank': rank,
            'base_only': base_rec,
            'fleet_zipf': fleet_rec,
            'tokens_per_sec_ratio': round(
                fleet_rec['tokens_per_sec']
                / base_rec['tokens_per_sec'], 3)
            if base_rec['tokens_per_sec'] else None,
            'adapters': st['adapters'],
            'sampled_tokens': st['counts'].get('sampled_tokens', 0),
            'retraced_programs': retraced,
        }],
    }
    try:
        from mxnet_tpu import observability
        payload['telemetry'] = observability.summary()
    except Exception as e:
        payload['telemetry'] = {'enabled': False,
                                'error': '%s: %s'
                                % (type(e).__name__, e)}
    if retraced:
        raise AssertionError(
            'adapter/sampling rotation retraced compiled programs '
            'after warmup: %r' % (retraced,))
    if st['adapters']['resident'] < fleet:
        raise AssertionError(
            '%d-adapter fleet served but only %d resident'
            % (fleet, st['adapters']['resident']))
    return payload


def run(status, args):
    from mxnet_tpu import serving

    max_batch = 8 if args.quick else 32
    frozen = _build_frozen(max_batch)
    frozen.warmup()        # compile the ladder outside the timed windows
    session = serving.InferenceSession(
        frozen, deadline_ms=args.deadline_ms, watchdog=False)
    seconds = 0.5 if args.quick else 3.0
    sweep = []
    try:
        for bucket in frozen.policy.buckets:
            rec = bench_bucket(session, bucket, seconds, args.clients)
            print('bucket %3d: %s req/s, p50 %s ms, p99 %s ms, bulk '
                  '%s ex/s' % (bucket, rec['requests_per_sec'],
                               rec['latency_p50_ms'],
                               rec['latency_p99_ms'],
                               rec['bulk_examples_per_sec']),
                  flush=True)
            sweep.append(rec)
    finally:
        session.close()

    recompiles = frozen.compile_count
    payload = {
        'metrics': [{
            'metric': 'serving_bucket_sweep',
            'unit': 'requests/s',
            'clients': args.clients,
            'deadline_ms': args.deadline_ms,
            'buckets': list(frozen.policy.buckets),
            'sweep': sweep,
            'recompile_count': recompiles,
            'recompile_bound': len(frozen.policy.buckets),
            'recompiles_bounded': recompiles
            <= len(frozen.policy.buckets),
        }],
    }
    try:
        from mxnet_tpu import observability
        payload['telemetry'] = observability.summary()
    except Exception as e:    # telemetry must never sink the artifact
        payload['telemetry'] = {'enabled': False,
                                'error': '%s: %s'
                                % (type(e).__name__, e)}
    if not payload['metrics'][0]['recompiles_bounded']:
        raise AssertionError(
            '%d programs compiled for a %d-bucket ladder'
            % (recompiles, len(frozen.policy.buckets)))
    return payload


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--out', default='BENCH_SERVING.json')
    p.add_argument('--quick', action='store_true',
                   help='CI-sized sweep (small ladder, short windows)')
    p.add_argument('--decode', action='store_true',
                   help='generation sweep: continuous vs flush '
                        'batching (tokens/s, TTFT, per-token latency)')
    p.add_argument('--paged', action='store_true',
                   help='paged-KV-cache sweep: max concurrent '
                        'sequences at equal HBM budget (slot vs '
                        'paged), shared-prefix TTFT A/B, and the '
                        'speculative-decoding tokens/s + acceptance-'
                        'rate leg')
    p.add_argument('--adapters', action='store_true',
                   help='multi-adapter sweep: Zipf rotation over an '
                        '8-LoRA fleet (half sampled) at zero '
                        'retraces, adapter-vs-base tokens/s A/B')
    p.add_argument('--clients', type=int, default=4)
    p.add_argument('--deadline-ms', type=float, default=2.0)
    args = p.parse_args()

    from mxnet_tpu.resilience import run_instrument
    if args.adapters:
        fn, label = run_adapters, 'bench_adapters'
    elif args.paged:
        fn, label = run_paged, 'bench_paged_decode'
    elif args.decode:
        fn, label = run_decode, 'bench_decode'
    else:
        fn, label = run, 'bench_serving'
    return run_instrument(label, lambda status: fn(status, args),
                          out=args.out)


if __name__ == '__main__':
    sys.exit(main())
