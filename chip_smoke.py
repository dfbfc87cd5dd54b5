"""chip_smoke.py — does the main path still start on the chip?

One process drives both halves of the system once, through the entry
points a user calls, with default knobs and seeded random weights:

  * trainer leg — ResNet-50 v1 (bf16, 224x224, batch 128 per chip)
    under ``ParallelTrainer`` on a ``dp`` mesh of every local chip:
    one compiling step plus four more;
  * server leg — a 12-layer, 768-unit, 30522-vocab paged decoder
    (``serving.freeze_decode`` -> ``InferenceSession`` ->
    ``ServingHTTPServer``) answering six concurrent ``/generate``
    requests and one ``/status`` over HTTP.

It is a smoke, not a measurement: it prints no rate and no utilisation,
and the wall seconds it logs are labelled as such. It exits non-zero,
and prints no JSON line, unless ``jax.default_backend() == 'tpu'``;
``--rehearse-cpu`` runs the same code at toy sizes on the CPU backend
so the script itself stays covered by the tier-1 tests. Any failed
check raises; nothing here catches a leg's exception.

The last two lines of stdout: the summary of both legs, a log line
``[chip_smoke] summary {...}`` whose JSON ends with ``"claim": null``,
and then the result, one JSON object with exactly these keys, the
device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
import argparse
import json
import math
import os
import sys
import threading
import time

import numpy as np

# (trainer, server) sizes. 'chip' is the full width of both models;
# 'rehearsal' only has to exercise every line of this script quickly.
SIZES = {
    'chip': {
        'net': 'resnet50_v1', 'classes': 1000, 'image': 224,
        'batch_per_chip': 128, 'dtype': 'bfloat16', 'steps': 5,
        'lm': dict(vocab=30522, units=768, hidden=3072, layers=12,
                   heads=12, max_len=512),
    },
    'rehearsal': {
        'net': 'resnet18_v1', 'classes': 10, 'image': 32,
        'batch_per_chip': 4, 'dtype': 'float32', 'steps': 5,
        'lm': dict(vocab=128, units=64, hidden=128, layers=2, heads=4,
                   max_len=128),
    },
}
PROMPT_LENS = (16, 23, 32, 41, 57, 64)
NEW_TOKENS = 32

# Agreement with the reference (model.full_forward on the same device at
# the same default matmul precision — on the chip one bf16 pass over
# fp32 data). Both sides round the same inputs the same way; what can
# differ is the accumulation order of differently shaped programs (the
# padded prefill bucket and the one-token paged step against the
# whole-sequence pass), so this is a tolerance, not the CPU rig's
# bit-identity. Needed on a v5e at the full width (CHANGES.md PR 21):
# none — max |diff| 0.0 over the 30522 first-token logits and 192/192
# decoded tokens the exact reference argmax. The bound is slack for
# other chips, not a measured error.
LOGIT_ATOL = {'tpu': 2e-2, 'cpu': 2e-3}
# loss of the fused step vs the gluon forward on the same batch
LOSS_RTOL = {'bfloat16': 5e-2, 'float32': 1e-3}


def log(msg):
    print('[chip_smoke] %s' % msg, flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError('chip_smoke check failed: %s' % what)
    log('ok: %s' % what)


def preamble(rehearse):
    import jax
    import jaxlib
    backend = jax.default_backend()
    if rehearse and backend != 'cpu':
        sys.stderr.write(
            'chip_smoke: --rehearse-cpu runs toy sizes and is for a CPU '
            'backend only; jax.default_backend() is %r. Run without the '
            'flag on the chip.\n' % backend)
        sys.exit(2)
    if not rehearse and backend != 'tpu':
        sys.stderr.write(
            'chip_smoke: jax.default_backend() is %r, not "tpu" — this '
            'script proves the program on the chip and refuses to pass '
            'anywhere else (--rehearse-cpu runs the toy-size rehearsal '
            'on a CPU backend).\n' % backend)
        sys.exit(2)
    import mxnet_tpu as mx
    devs = jax.devices()
    device = {'platform': devs[0].platform, 'kind': devs[0].device_kind,
              'count': len(devs)}
    from importlib import metadata
    try:
        libtpu = metadata.version('libtpu')
    except metadata.PackageNotFoundError:    # a CPU-only installation
        libtpu = None
    log('platform %s, device_kind %r, %d device(s)'
        % (device['platform'], device['kind'], device['count']))
    log('jax %s, jaxlib %s, libtpu %s'
        % (jax.__version__, jaxlib.__version__, libtpu))
    cache_dir = jax.config.jax_compilation_cache_dir
    log('compile cache directory: %s' % cache_dir)
    # the placement rule (mxnet_tpu.config.configure_compile_cache)
    placed = os.environ.get('JAX_COMPILATION_CACHE_DIR') or os.path.join(
        os.path.dirname(os.path.abspath(mx.__file__)), os.pardir,
        '.jax_cache')
    check(os.path.abspath(cache_dir) == os.path.abspath(placed),
          'the cache is where JAX_COMPILATION_CACHE_DIR puts it, else at '
          '<repo>/.jax_cache')
    return device, {'jax': jax.__version__, 'jaxlib': jaxlib.__version__,
                    'libtpu': libtpu, 'compile_cache_dir': cache_dir}


# -- trainer leg --------------------------------------------------------------

def trainer_leg(size, device):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, parallel
    from mxnet_tpu.gluon import model_zoo

    ndev = device['count']
    batch = size['batch_per_chip'] * ndev
    image, dtype = size['image'], size['dtype']
    log('trainer leg: %s %s %dx%d, batch %d per chip x dp=%d'
        % (size['net'], dtype, image, image, size['batch_per_chip'],
           ndev))

    mx.random.seed(0)
    np.random.seed(0)     # the initializers draw from numpy's generator
    net = model_zoo.vision.get_model(size['net'],
                                     classes=size['classes'])
    net.initialize(mx.init.Xavier())
    if dtype == 'bfloat16':
        net.cast('bfloat16')
    net.hybridize(static_alloc=True, static_shape=True)
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    x = nd.array(rs.uniform(-1, 1, (batch, 3, image, image)),
                 dtype=dtype)
    y = nd.array(rs.randint(0, size['classes'], (batch,)))

    # reference: the gluon forward (the CachedOp road to jit) in train
    # mode on the same batch and the same initial parameters
    t0 = time.perf_counter()
    with autograd.train_mode():
        ref_loss = float(L(net(x), y).mean().asscalar())
    ref_s = time.perf_counter() - t0
    check(math.isfinite(ref_loss), 'reference forward loss finite (%.4f)'
          % ref_loss)

    params = net.collect_params()
    watched = [p for name, p in sorted(params.items())
               if name.endswith('weight')]
    watched = [watched[0], watched[-1]]
    before = [p.data().asnumpy().astype('float32') for p in watched]

    mesh = parallel.create_mesh({'dp': ndev}, devices=jax.devices())
    pt = parallel.ParallelTrainer(
        net, L, 'sgd', {'learning_rate': 0.1, 'momentum': 0.9,
                        'wd': 1e-4}, mesh)
    losses, walls = [], []
    for i in range(size['steps']):
        t0 = time.perf_counter()
        loss = pt.step(x, y)
        loss.wait_to_read()
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss.asscalar()))
        check(math.isfinite(losses[-1]),
              'step %d loss finite (%.4f)' % (i, losses[-1]))
    log('compiling step %.1f s; later steps %s s wall each (smoke, not '
        'a measurement)' % (walls[0], ['%.3f' % w for w in walls[1:]]))

    rtol = LOSS_RTOL[dtype]
    check(abs(losses[0] - ref_loss) <= rtol * abs(ref_loss),
          'fused step 0 loss %.4f agrees with the gluon forward %.4f '
          '(rtol %g)' % (losses[0], ref_loss, rtol))
    check(pt._jitted is not None and pt._jitted._cache_size() == 1,
          'the fused program ran and was traced once (%d steps, cache '
          'size %d)' % (size['steps'], pt._jitted._cache_size()))
    after = [p.data().asnumpy().astype('float32') for p in watched]
    for p, b, a in zip(watched, before, after):
        check(np.isfinite(a).all() and not np.array_equal(a, b),
              'parameter %s changed and is finite' % p.name)

    platform = device['platform']
    arrays = [p.data()._data for p in params.values()] \
        + list(pt._state_leaves) + [loss._data]
    check(all(d.platform == platform
              for a in arrays for d in a.devices()),
          '%d parameter, state and output arrays live on %s devices'
          % (len(arrays), platform))
    record = {'net': size['net'], 'dtype': dtype, 'image': image,
              'batch_per_chip': size['batch_per_chip'], 'dp': ndev,
              'steps': size['steps'], 'losses': losses,
              'reference_loss': ref_loss,
              'compile_s': round(walls[0], 2),
              'reference_forward_s': round(ref_s, 2)}
    if ndev > 1:
        record.update(multichip_checks(pt, x, watched[0], device))
    return record


def multichip_checks(pt, x, param, device):
    """dp > 1: the batch is split, the parameters are copied, and the
    step reduces gradients across chips."""
    import jax
    ndev = device['count']
    devs = set(jax.devices())
    xd = pt._put_data(x._data, pt._data_shardings[0][0])
    shards = xd.addressable_shards
    check({s.device for s in shards} == devs
          and all(s.data.shape[0] == x.shape[0] // ndev
                  for s in shards),
          'every chip holds a %d-row shard of the %d-row batch'
          % (x.shape[0] // ndev, x.shape[0]))
    w = param.data()._data
    check({s.device for s in w.addressable_shards} == devs
          and all(s.data.shape == w.shape for s in w.addressable_shards),
          'every chip holds a full copy of %s' % param.name)
    in_use = []
    for d in jax.devices():
        stats = d.memory_stats()   # None where the backend has none
        in_use.append(None if stats is None else stats['bytes_in_use'])
    # chip 0 also holds what the user put there: the unsharded batch
    # (nd.array's default context) and the reference forward's output
    log('bytes_in_use per device: %s' % in_use)
    if device['platform'] == 'tpu':
        resident = sum(a.nbytes for a in pt._param_arrays) \
            + sum(a.nbytes for a in pt._state_leaves)
        check(all(b is not None and b >= resident for b in in_use),
              'each of the %d chips holds at least the %d bytes of '
              'parameters and optimizer state' % (ndev, resident))
    text = pt.compiled_text()
    check('all-reduce' in text, 'the compiled step contains an '
          'all-reduce')
    return {'bytes_in_use': in_use, 'all_reduce_in_step': True}


# -- server leg ---------------------------------------------------------------

def _http(port, method, path, body=None, timeout=120.0):
    import http.client
    conn = http.client.HTTPConnection('127.0.0.1', port, timeout=timeout)
    try:
        conn.request(method, path,
                     None if body is None else json.dumps(body),
                     {'Content-Type': 'application/json'})
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _generate(port, prompt, stream, out, i):
    status, text = _http(port, 'POST', '/generate',
                         {'tokens': prompt, 'max_new_tokens': NEW_TOKENS,
                          'stream': stream})
    # stream=true answers NDJSON: token lines, then the done line
    done = json.loads(text.strip().splitlines()[-1]) if text.strip() \
        else {}
    out[i] = (status, done)


def server_leg(size, device):
    import jax
    from mxnet_tpu import observability as obs
    from mxnet_tpu import serving
    from mxnet_tpu.serving import decode

    cfg = size['lm']
    vocab = cfg['vocab']
    log('server leg: paged decoder %s on %s (the server takes no device '
        'argument: it runs on the default device)' % (cfg,
                                                      jax.devices()[0]))
    model, params = decode.init_transformer_lm(seed=0, **cfg)
    prog = serving.freeze_decode(model, params, max_len=cfg['max_len'])
    check(prog.paged, 'freeze_decode chose the paged program by default')
    ladder = list(prog.prefill_buckets)
    t0 = time.perf_counter()
    sess = serving.InferenceSession(prog, warmup=True)
    warm_s = time.perf_counter() - t0
    log('warm-up compiled %d programs in %.1f s: %s'
        % (prog.compile_count, warm_s,
           {k: round(v, 1) for k, v in prog.compile_seconds.items()}))
    traces_warm = dict(prog.trace_counts)
    compiled_warm = prog.compile_count
    trips0 = obs.serving_instruments().breaker_trips.value
    fallbacks0 = obs.serving_instruments().fallbacks.value

    rs = np.random.RandomState(1)
    prompts = [[int(t) for t in rs.randint(0, vocab, n)]
               for n in PROMPT_LENS]
    srv = serving.ServingHTTPServer(sess, 0).start()
    try:
        results = [None] * len(prompts)
        threads = [threading.Thread(
            target=_generate,
            args=(srv.port, p, i % 2 == 0, results, i))
            for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(180.0)
        burst_s = time.perf_counter() - t0
        check(not any(t.is_alive() for t in threads)
              and all(r is not None for r in results),
              'all %d concurrent /generate requests returned'
              % len(prompts))
        st_code, st_text = _http(srv.port, 'GET', '/status')
    finally:
        srv.stop()
    log('%d requests x %d tokens answered in %.2f s wall (smoke, not a '
        'measurement)' % (len(prompts), NEW_TOKENS, burst_s))

    streams = []
    for i, (code, done) in enumerate(results):
        toks = done.get('tokens') or []
        check(code == 200 and 'error' not in done
              and done.get('degraded') is False
              and done.get('finish_reason') == 'length'
              and len(toks) == NEW_TOKENS
              and all(isinstance(t, int) and 0 <= t < vocab
                      for t in toks),
              'request %d (prompt %d, stream=%s): 200, %d in-vocabulary '
              'tokens, not degraded' % (i, len(prompts[i]), i % 2 == 0,
                                        len(toks)))
        streams.append(toks)

    status = json.loads(st_text)
    counts = status['decode']['counts']
    check(st_code == 200 and status['status'] == 'ok'
          and status['breaker'] == 'closed',
          '/status: 200, status ok, breaker closed')
    check(counts['fallback_tokens'] == 0
          and counts['tokens'] == len(prompts) * NEW_TOKENS,
          'decode.counts: %d tokens on the device, 0 fallback tokens'
          % counts['tokens'])
    events = [e.get('kind') for e in obs.get_recorder().events()]
    check(obs.serving_instruments().breaker_trips.value == trips0
          and obs.serving_instruments().fallbacks.value == fallbacks0
          and 'breaker_open' not in events
          and 'serve_fallback' not in events,
          'no breaker trip and no serve_fallback event at any point')
    # token-emitting programs: the prefill ladder + ONE step; the paged
    # layout adds its copy-on-write page copy (program.py: ladder + 2)
    token_programs = [k for k in prog.trace_counts if k != 'copy']
    check(len(token_programs) <= len(ladder) + 1
          and prog.compile_count <= len(ladder) + 2,
          'programs compiled: %d token-emitting <= ladder %d + 1 '
          '(+ copy_page = %d in all)' % (len(token_programs),
                                         len(ladder), prog.compile_count))
    check(prog.trace_counts == traces_warm
          and prog.compile_count == compiled_warm
          and all(v == 1 for v in traces_warm.values()),
          'zero retraces after warm-up')

    record = {'lm': cfg, 'requests': len(prompts),
              'new_tokens': NEW_TOKENS, 'ladder': ladder,
              'compiled': prog.compile_count,
              'warmup_compile_s': round(warm_s, 2),
              'device': str(jax.devices()[0])}
    record.update(reference_checks(model, prog, prompts, streams,
                                   device))
    sess.close()
    return record


def reference_checks(model, prog, prompts, streams, device):
    """Teacher-forced agreement with ``model.full_forward``: one
    reference pass over prompt + generated tokens scores every position
    the engine decoded, so a wrong page table, cache write or step
    program shows up as a token the reference would not have picked."""
    import jax
    import jax.numpy as jnp
    atol = LOGIT_ATOL[device['platform']]
    width = max(len(p) for p in prompts) + NEW_TOKENS
    toks = np.zeros((len(prompts), width), 'int32')
    for i, (p, s) in enumerate(zip(prompts, streams)):
        toks[i, :len(p)] = p
        toks[i, len(p):len(p) + len(s)] = s
    ref = jax.jit(model.full_forward)
    logits = np.asarray(ref(prog._params, jnp.asarray(toks)))
    check(np.isfinite(logits).all() and logits.shape
          == (len(prompts), width, model.vocab),
          'reference logits finite, shape %s' % (logits.shape,))
    exact, worst = 0, 0.0
    for i, (p, s) in enumerate(zip(prompts, streams)):
        for j, tok in enumerate(s):
            row = logits[i, len(p) - 1 + j]
            gap = float(row.max() - row[tok])
            exact += int(gap == 0.0)
            worst = max(worst, gap)
    total = len(prompts) * NEW_TOKENS
    check(worst <= atol,
          'every decoded token is the reference argmax or within %g of '
          'it (%d/%d exact, worst gap %.4f)' % (atol, exact, total,
                                                worst))

    # first-token logits of the engine's own prefill program
    p = prompts[0]
    from mxnet_tpu.serving.decode.paged import pages_for
    ids = list(range(1, 1 + pages_for(len(p), prog.page_size)))
    _pool, tok, got = prog.run_prefill(prog.new_cache(), p, ids)
    want = logits[0, len(p) - 1]
    diff = float(np.abs(got - want).max())
    check(diff <= atol and tok == streams[0][0],
          'first-token logits within %g of full_forward (max |diff| '
          '%.4f over %d logits, spread %.2f)'
          % (atol, diff, want.size, float(want.max() - want.min())))
    return {'logit_atol': atol, 'first_token_logit_max_abs_diff': diff,
            'decoded_tokens_exact_argmax': '%d/%d' % (exact, total),
            'decoded_tokens_worst_gap': worst}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--rehearse-cpu', action='store_true',
                    help='toy sizes on a CPU backend (tier-1 test of '
                         'this script); never a chip result')
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device, versions = preamble(args.rehearse_cpu)
    size = SIZES['rehearsal' if args.rehearse_cpu else 'chip']
    trainer = trainer_leg(size, device)
    server = server_leg(size, device)
    cache_dir = versions['compile_cache_dir']
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    check(entries > 0, 'the compile cache directory holds %d entries'
          % entries)
    summary = {'versions': versions,
               'rehearsal': bool(args.rehearse_cpu),
               'trainer': trainer, 'server': server,
               'wall_s': round(time.perf_counter() - t0, 1),
               'claim': None}
    log('summary %s' % json.dumps(summary))
    # the result line: these keys and no others (the driver parses it)
    print(json.dumps({'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
    main()
