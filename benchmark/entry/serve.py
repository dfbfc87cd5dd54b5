"""Entry ``serve``: drives ``POST /generate`` on the program's HTTP server
with the cell's traffic (``benchmark/loadgen.py``) and, once the window has
closed and the server is gone, runs the plain reference over a seeded
sample of the requests it finished and compares each served token's logit
with the reference's best.
"""
import asyncio
import gc
import importlib
import random
import threading
import time

import numpy as np

from .. import checks, loadgen
from ..tracing import Tracer


def served_sample(requests, seed, count):
    """``count`` finished requests drawn from the seed, and the longest."""
    done = [r for r in requests if r.error is None and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:count]


def widest_gap(ref, cfg, seed, sample, dtype=None, weights=None):
    """Reference logits for the sample; the widest gap of a served token
    below the reference's best and, for a control ``dtype``, the widest gap
    of the token that the lower precision puts first."""
    weights = ref.make_weights(cfg, seed) if weights is None else weights
    prompts = [r.prompt for r in sample]
    outputs = [r.tokens for r in sample]
    logits = ref.next_token_logits(cfg, weights, prompts, outputs)
    if dtype is None:
        gaps = [checks.logit_gaps(lg, out)
                for lg, out in zip(logits, outputs)]
    else:
        low = ref.next_token_logits(cfg, weights, prompts, outputs,
                                    dtype=dtype)
        gaps = [checks.logit_gaps(lg, lo.argmax(-1))
                for lg, lo in zip(logits, low)]
    flat = np.concatenate(gaps)
    return float(flat.max()), int(flat.size), float((flat == 0).mean())


def warm(ctx, port, cfg, traffic):
    """One short request through every prefill bucket and the step, each
    with tokens of its own: a prompt that shares a prefix with an earlier
    one is served from the prefix cache, one decode step a suffix token."""
    rng = random.Random(ctx.seed + 17)
    for bucket in cfg['deployment']['prefill_buckets']:
        prompt = [rng.randrange(cfg['vocab_size']) for _ in range(bucket - 3)]
        req = loadgen.Request(-1, None, prompt, 4)
        asyncio.run(loadgen._post(port, req, loadgen.streamed(traffic),
                                  time.time))
        if req.error:
            raise RuntimeError('warm-up request failed: %s' % req.error)
    ctx.mark('warm-up requests answered (%d programs lowered so far)'
             % ctx.compiles.count)


def run(ctx, build=None):
    cfg, traffic = ctx.config, ctx.traffic
    ref = importlib.import_module('benchmark.reference.' + cfg['family'])
    if build is None:
        build = importlib.import_module('benchmark.systems.'
                                        + cfg['family']).Server
    ctx.mark('imports done')
    weights = ref.make_weights(cfg, ctx.seed)
    ctx.mark('weights made')
    system = build(cfg, weights, ctx.trace)
    del weights
    ctx.mark('server up, every program compiled or loaded')
    warm(ctx, system.port, cfg, traffic)
    lowered = ctx.compiles.count
    tracer = Tracer(ctx)
    at_open = {}

    def on_open():
        at_open['counts'] = system.counts()
        threading.Thread(target=tracer.start, daemon=True).start()

    def on_close():
        at_open['counts_close'] = system.counts()
        threading.Thread(target=tracer.stop, daemon=True).start()

    requests, t_open = loadgen.drive(system.port, traffic,
                                     cfg['vocab_size'], ctx.seed,
                                     ctx.seconds, on_open, on_close)
    tracer.stop()
    ctx.mark('window closed and every request answered or given up')
    t_close = t_open + ctx.seconds
    setup_s = t_open - ctx.started
    counts1 = at_open.get('counts_close') or system.counts()
    spans = system.spans() if ctx.trace else []
    in_window = ctx.compiles.count - lowered
    peak = max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
               for d in ctx.devices)
    xp = tracer.reduce(len(ctx.devices))
    ctx.mark('trace reduced')
    slots = system.slots
    system.close()
    del system
    gc.collect()

    measured = [r for r in requests if r.measured]
    failed = [r for r in requests if r.error is not None]
    delivered = [(t, len(r.prompt) + j) for r in requests
                 for j, t in enumerate(r.token_times)
                 if t_open <= t < t_close]
    never = float('inf')
    timed = [r for r in measured if r.due is not None]    # the open loop's
    ttft = [(r.first - (t_open + r.due)) * 1e3 if r.first else never
            for r in timed]
    tpot = [(r.last - r.first) / (len(r.tokens) - 1) * 1e3
            if r.error is None and len(r.tokens) > 1 else never
            for r in timed]
    end = {'serve_tokens_per_s': len(delivered) / ctx.seconds,
           'setup_s': setup_s}
    if ttft:
        end['ttft_p95_ms'] = min(loadgen.percentile(ttft, 95), 1e9)
        end['tpot_p95_ms'] = min(loadgen.percentile(tpot, 95), 1e9)
    halves = [[x for r, x in zip(timed, ttft)
               if (r.due < ctx.seconds / 2) == first]
              for first in (True, False)]
    c0 = at_open.get('counts', {})
    steps = counts1.get('steps', 0) - c0.get('steps', 0)
    facts = {
        'end_to_end': end, 'attempted': len(requests),
        'failed': len(failed), 'memory_peak_bytes': peak,
        'window_s': ctx.seconds, 'compiles_in_window': in_window,
        'config': cfg, 'traffic': traffic, 'peaks': ctx.peaks,
        'chips': ctx.chips, 'xplane': xp, 'slots': slots,
        'steps': steps,
        'ttft_halves': [loadgen.percentile(h, 95) for h in halves],
        'engine_tokens': counts1.get('tokens', 0) - c0.get('tokens', 0),
        'engine_prefills': counts1.get('prefills', 0)
        - c0.get('prefills', 0),
        'live_kv_tokens_per_step':
            sum(n for _, n in delivered) / steps if steps else None,
        'active_per_step': len(delivered) / steps if steps else None,
        'series': {
            'lateness_ms': [(r.sent - (t_open + r.due)) * 1e3
                            for r in timed if r.sent],
            'request_latency_ms': [(r.last - r.sent) * 1e3
                                   for r in measured
                                   if r.last and r.sent],
            'queue_wait_ms': [(s['t1'] - s['t0']) * 1e3 for s in spans
                              if s.get('name') == 'eng.queue_wait'
                              and t_open <= s['t1'] < t_close]}}

    verdict = checks.Verdict()
    verdict.hold('compiles_in_window', in_window, 0)
    verdict.hold('never_answered', len(failed), 0)
    for r in failed[:3]:
        verdict.note('failed_%d' % r.rid, r.error[:200])
    wrong = [r for r in requests
             if r.error is None and len(r.tokens) != r.max_new]
    verdict.hold('wrong_length', len(wrong), 0)
    sample = served_sample(requests, ctx.seed,
                           int(traffic['checked_requests']))
    if sample:
        gap, n, exact = widest_gap(ref, cfg, ctx.seed, sample)
        ctx.mark('reference run over %d tokens' % n)
        verdict.hold('logit_gap_max', gap, ctx.limits['logit_gap_max'])
        verdict.note('tokens_compared', n)
        verdict.note('share_exact_argmax', exact)
    else:
        verdict.hold('requests_finished', 1, 0)
    facts['verdict'] = verdict
    facts['sample'] = sample
    return facts


def control(ctx):
    """Upper readings: a short window of the cell's own traffic, then, at
    each position of the sampled prompts and served tokens, the gap of the
    token that each lower precision puts first."""
    facts = run(ctx)
    cfg = ctx.config
    ref = importlib.import_module('benchmark.reference.' + cfg['family'])
    weights = ref.make_weights(cfg, ctx.seed)
    out = {'program': {k: r['value']
                       for k, r in facts['verdict'].rows.items()}}
    for dtype in (cfg['precision']['control'], 'bfloat16'):
        gap, n, exact = widest_gap(ref, cfg, ctx.seed, facts['sample'],
                                   dtype=dtype, weights=weights)
        out['control_' + dtype] = {
            'logit_gap_max': gap, 'tokens_compared': n,
            'share_exact_argmax': exact,
            'correct': gap <= ctx.limits['logit_gap_max']}
    return out
