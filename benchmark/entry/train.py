"""Entry ``train``: drives one compiled train step with its state.

Set-up builds the program once (``benchmark/systems/<family>.py``), drives
it from the seed through its first steps by the window's own call and feed,
reading the state back after the first and the last of them, and hands the
same object to the window. After the window the program is freed and the
plain reference (``benchmark/reference/<family>.py``) follows those first
steps from the same seed; ``correct`` compares the two.
"""
import collections
import gc
import importlib
import time

import numpy as np

from .. import checks
from ..tracing import Tracer, annotate

# steps in flight before the host waits for a loss: a training loop reads
# its loss every few steps, and at 2 a host stall of two step times (140 ms
# for BERT-base) already idles the device
PIPELINE_DEPTH = 6


def compare(verdict, limits, prog, ref):
    """The numbers of a training cell. ``prog`` and ``ref`` hold ``losses``,
    ``grad`` (first step, as the optimizer got it) and ``delta`` (change of
    the parameters over the steps followed), by leaf name."""
    for i, (a, b) in enumerate(zip(prog['losses'], ref['losses']), 1):
        verdict.hold('loss_gap_step%d' % i, abs(a - b) / abs(b),
                     limits['loss_gap_step%d' % i])
    gap, leaf = checks.norm_gaps(prog['grad'], ref['grad'])
    verdict.hold('grad_norm_gap', gap, limits['grad_norm_gap'])
    verdict.note('grad_norm_gap_leaf', leaf)
    # a leaf with no gradient to speak of moves under Adam by round-off
    norms = {k: float(np.linalg.norm(np.asarray(v, 'float64')))
             for k, v in ref['grad'].items()}
    floor = 1e-3 * float(np.median(list(norms.values())))
    moved = [k for k, n in norms.items() if n >= floor]
    gap, leaf = checks.norm_gaps({k: prog['delta'][k] for k in moved},
                                    {k: ref['delta'][k] for k in moved})
    verdict.hold('update_norm_gap', gap, limits['update_norm_gap'])
    verdict.note('update_norm_gap_leaf', leaf)
    verdict.note('leaves_without_gradient', len(norms) - len(moved))


def follow(ref, cfg, seed, batches, **kw):
    """The reference (or, lowered, the control) over the first steps."""
    w0 = ref.make_weights(cfg, seed)
    losses, grad, w = ref.train(cfg, w0, batches, **kw)
    host = lambda t: {k: np.asarray(v) for k, v in t.items()}  # noqa: E731
    w0, w = host(w0), host(w)
    return {'losses': losses, 'grad': host(grad),
            'delta': {k: w[k] - w0[k] for k in w}}, w0


def run(ctx, build=None):
    cfg, traffic = ctx.config, ctx.traffic
    ref = importlib.import_module('benchmark.reference.' + cfg['family'])
    if build is None:
        build = importlib.import_module('benchmark.systems.'
                                        + cfg['family']).Trainer
    batches = ref.make_batches(cfg, traffic, ctx.seed,
                               traffic['distinct_batches'])
    ctx.mark('imports done, batches made')
    system = build(cfg, traffic, ref.make_weights(cfg, ctx.seed),
                   ctx.devices)
    ctx.mark('trainer built')
    followed = int(traffic['followed_steps'])
    beta1 = cfg['optimizer']['beta1']
    prog = {'losses': []}
    for i in range(followed):
        prog['losses'].append(system.wait(system.step(batches[i])))
        if i == 0:
            prog['grad'] = {k: m / (1.0 - beta1)
                            for k, (_w, m) in system.state().items()}
    after = {k: w for k, (w, _m) in system.state().items()}
    lowered = ctx.compiles.count
    ctx.mark('first %d steps followed, state read back' % followed)
    setup_s = time.time() - ctx.started

    tracer = Tracer(ctx)
    pending = collections.deque()
    done, i = 0, followed
    tracer.start()
    t0 = time.time()
    while time.time() - t0 < ctx.seconds:
        with annotate('step'):
            pending.append(system.step(batches[i % len(batches)]))
        i += 1
        with annotate('wait_loss'):
            while len(pending) > PIPELINE_DEPTH:
                system.wait(pending.popleft())
                done += 1
    with annotate('wait_loss'):
        while pending:
            system.wait(pending.popleft())
            done += 1
    window_s = time.time() - t0
    tracer.stop()

    in_window = ctx.compiles.count - lowered
    peak = max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
               for d in ctx.devices)
    system.close()
    del system
    gc.collect()

    samples = done * traffic['batch']
    rate = samples / window_s
    facts = {'end_to_end': {'train_samples_per_s': rate, 'setup_s': setup_s},
             'attempted': done, 'failed': 0, 'memory_peak_bytes': peak,
             'window_s': window_s, 'steps': done, 'samples': samples,
             'compiles_in_window': in_window, 'config': cfg,
             'traffic': traffic, 'peaks': ctx.peaks, 'chips': ctx.chips}
    facts['xplane'] = tracer.reduce(len(ctx.devices))

    verdict = checks.Verdict()
    verdict.hold('compiles_in_window', in_window, 0)
    ctx.mark('window closed, program freed')
    refd, w0 = follow(ref, cfg, ctx.seed, batches[:followed])
    ctx.mark('reference followed %d steps' % followed)
    prog['delta'] = {k: after[k] - w0[k] for k in after}
    compare(verdict, ctx.limits, prog, refd)
    facts['verdict'] = verdict
    return facts


def control(ctx):
    """Upper readings, with the reference in the program's place: the
    configuration's control precision, and the fault of a step that leaves
    half of the batch out. No window and no program. Returns
    {reading name: {number: value}}."""
    cfg, traffic = ctx.config, ctx.traffic
    ref = importlib.import_module('benchmark.reference.' + cfg['family'])
    followed = int(traffic['followed_steps'])
    batches = ref.make_batches(cfg, traffic, ctx.seed,
                               traffic['distinct_batches'])[:followed]
    refd, _ = follow(ref, cfg, ctx.seed, batches)
    out = {}
    for name, kw in (('control_' + cfg['precision']['control'],
                      {'dtype': cfg['precision']['control']}),
                     ('fault_half_batch', {'drop_half': True})):
        other, _ = follow(ref, cfg, ctx.seed, batches, **kw)
        verdict = checks.Verdict()
        compare(verdict, ctx.limits, other, refd)
        out[name] = {k: r['value'] for k, r in verdict.rows.items()}
        out[name]['correct'] = verdict.correct
    return out
