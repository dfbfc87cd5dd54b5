"""The readers of the spans and names the program puts into a profiler
trace itself (PR 27), each on hand-made facts: a hit, nothing to read
(``None``, so the metric is left out of the line), and the cut of
``idle_gaps`` to its ten longest rows."""
import json
import os

import pytest

import bench_tiny
from benchmark.readers import (idle_gap_ms_per_step, idle_gap_share,
                               modules_device_share, percentile_ms)

REPO = bench_tiny.REPO

# what a traced serving run hands a reader, as benchmark/xplane.py and
# benchmark/entry/serve.py shape it
GAPS = [['DevicePut', 0.7], ['eng.tick.emit', 0.2],
        ['eng.tick.build_inputs', 0.1], ['eng.tick', 0.04],
        ['no span', 0.06], ['eng.wait_work', 0.5],
        ['between device ops', 0.01]]
MODULES = {'jit_fn_step(1)': [4.2, 30], 'jit_prefill_b128(2)': [0.3, 5],
           'jit_prefill_b64(3)': [0.1, 4], 'jit_page_copy(4)': [0.01, 2]}
FACTS = {'steps': 34, 'xplane': {'idle_gaps': GAPS, 'modules': MODULES,
                                 'busy_s': 5.0},
         'series': {'queue_wait_ms': [float(i) for i in range(1, 101)]}}
PARENT = {'steps': 34, 'series': {'queue_wait_ms': []},
          'xplane': {'idle_gaps': [['DevicePut', 0.7], ['no span', 0.3]],
                     'modules': {'jit_fn(1)': [4.2, 30],
                                 'jit_fn(2)': [0.3, 5]},
                     'busy_s': 5.0}}


@pytest.mark.parametrize('reader,args,facts,want', [
    # idle under the scheduler's own spans, per step: eng.tick and its
    # phases, and not eng.wait_work (idle for want of requests)
    (idle_gap_ms_per_step, {'prefix': 'eng.tick'}, FACTS,
     1e3 * 0.34 / 34),
    (idle_gap_ms_per_step, {'prefix': 'eng.tick'}, PARENT, None),
    (idle_gap_ms_per_step, {'prefix': 'eng.tick'},
     dict(FACTS, steps=0), None),
    (idle_gap_ms_per_step, {'prefix': 'eng.tick'}, {'steps': 3}, None),
    (idle_gap_share, {'name': 'no span'}, FACTS, 100 * 0.06 / 1.61),
    (idle_gap_share, {'name': 'no span'}, PARENT, 30.0),
    (idle_gap_share, {'name': 'no span'},
     {'xplane': {'idle_gaps': [['DevicePut', 0.5]]}}, 0.0),
    (idle_gap_share, {'name': 'no span'},
     {'xplane': {'idle_gaps': []}}, None),
    (idle_gap_share, {'name': 'no span'}, {'xplane': None}, None),
    (modules_device_share, {'module': '^jit_prefill_'}, FACTS,
     100 * 0.4 / 5.0),
    (modules_device_share, {'module': '^jit_prefill_'}, PARENT, None),
    (modules_device_share, {'module': '^jit_fn'}, FACTS,
     100 * 4.2 / 5.0),
    (modules_device_share, {'module': '^jit_prefill_'}, {}, None),
    (percentile_ms, {'series': 'queue_wait_ms', 'q': 95}, FACTS, 95.0),
    (percentile_ms, {'series': 'queue_wait_ms', 'q': 95}, PARENT, None),
])
def test_reader_on_hand_made_facts(reader, args, facts, want):
    got = reader.read(facts, **args)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_gap_readers_see_only_the_ten_rows_that_reach_facts():
    """``xplane.reduce_planes`` cuts ``idle_gaps`` to its ten longest
    rows: a phase beyond them is missing from the sum, which is why the
    phases are few (PERF.md §7)."""
    from benchmark import xplane
    ms = 1_000_000
    names = ['eng.tick.p%02d' % i for i in range(12)]
    # twelve gaps of 12, 11, ... 1 ms, each under a span of its own
    ops, spans, at = [(0, ms, '%f = f32[8]{0} fusion(f32[8] %p)')], [], ms
    for i, name in enumerate(names):
        gap = (12 - i) * ms
        spans.append((at, at + gap, name))
        at += gap
        ops.append((at, at + ms, '%f = f32[8]{0} fusion(f32[8] %p)'))
        at += ms
    planes = {'/device:TPU:0': {'XLA Ops': ops, 'XLA Modules': []},
              '/host:CPU': {'python': spans}}
    xp = xplane.reduce_planes(planes, 1)
    assert [n for n, _ in xp['idle_gaps']] == names[:10]
    facts = {'steps': 1, 'xplane': xp}
    assert idle_gap_ms_per_step.read(facts, prefix='eng.tick') \
        == pytest.approx(sum(range(3, 13)))         # 2 and 1 ms are cut
    assert idle_gap_share.read(facts, name='no span') == 0.0


@pytest.mark.parametrize('name', [
    'queue_wait_p95_ms', 'prefill_device_share.chat',
    'prefill_device_share.serve', 'scheduler_gap_ms_per_step.chat',
    'scheduler_gap_ms_per_step.serve', 'idle_unattributed_share.chat',
    'idle_unattributed_share.serve'])
def test_new_metric_reads_the_programs_own_names(name):
    """Each entry of PR 27 names its cell, reads through its file, and
    the pattern in the file is one the program really writes."""
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    entry, = [m for m in bench['per_layer'] if m['name'] == name]
    cell = 'gpt1-chat-steady' if name.endswith(('.chat', '_ms')) \
        else 'gpt1-batch-saturated'
    assert entry['workloads'] == [cell]
    with open(os.path.join(REPO, 'benchmark', 'metrics',
                           name + '.json')) as f:
        spec = json.load(f)
    from mxnet_tpu.observability import spans
    args = spec['args']
    if 'prefix' in args:
        assert args['prefix'] in spans.PHASES
        assert not 'eng.wait_work'.startswith(args['prefix'])
    if 'module' in args:
        import re
        assert re.search(args['module'], 'jit_prefill_b128(7)')
        assert not re.search(args['module'], 'jit_fn_step(7)')
    if 'name' in args:
        from benchmark import xplane
        assert xplane.attribute([(0, 10_000)], [])[0][0] == args['name']
