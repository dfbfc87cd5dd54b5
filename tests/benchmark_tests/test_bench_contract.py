"""Static and arithmetic checks of the benchmark: the contract's names and
files, the operation counts against hand-computed layers, the load
generator's schedule, the trace reduction, and the refusal to measure
without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import bench_tiny
from benchmark import loadgen, xplane
from benchmark.flops import bert as bert_flops
from benchmark.flops import decoder_lm as lm_flops

REPO = bench_tiny.REPO
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')


def _bench():
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(REPO, 'benchmark', *parts)) as f:
        return json.load(f)


def test_benchmark_json_keys_names_and_units():
    b = _bench()
    assert set(b) == {'command', 'paths', 'run_seconds', 'configs',
                      'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= b['run_seconds'] <= 51
    for c in b['configs']:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert NAME.match(c['name']) and all(NAME.match(k)
                                             for k in c['reduced'])
        assert any(c['file'].startswith(p + '/') for p in b['paths'])
    for w in b['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(w['name']) and NAME.match(w['traffic'])
        assert w['chips'] in (1, 4) and len(w['why']) <= 200
    for m in b['end_to_end']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.1
    for m in b['per_layer']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better',
                                          'source', 'layer', 'moves'}
        assert m['source'] in ('device_trace', 'program_span',
                               'program_counter', 'host_clock')
    names = [m['name'] for m in b['end_to_end'] + b['per_layer']]
    assert len(names) == len(set(names))
    for m in b['end_to_end'] + b['per_layer']:
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert m['better'] in ('lower', 'higher')
    assert 'setup_s' in names
    four = sum(w['chips'] == 4 for w in b['workloads'])
    assert four <= max(1, len(b['workloads']) // 4)


def test_every_cell_and_metric_has_its_files():
    b = _bench()
    configs = {c['name']: c for c in b['configs']}
    cells = {w['name'] for w in b['workloads']}
    end = {m['name']: m for m in b['end_to_end']}
    for w in b['workloads']:
        cfg = configs[w['config']]
        with open(os.path.join(REPO, cfg['file'])) as f:
            body = json.load(f)
        assert body['reduced'] == cfg['reduced']
        assert os.path.exists(os.path.join(
            REPO, 'benchmark', 'entry', body['entry'] + '.py'))
        for kind in ('reference', 'systems', 'flops'):
            assert os.path.exists(os.path.join(
                REPO, 'benchmark', kind, body['family'] + '.py'))
        _load('traffic', w['traffic'] + '.json')
        assert _load('limits', w['name'] + '.json')
        reported = [m for m in b['end_to_end']
                    if w['name'] in m.get('workloads', cells)]
        assert len(reported) >= 2
    for m in b['per_layer']:
        spec = _load('metrics', m['name'] + '.json')
        assert os.path.exists(os.path.join(
            REPO, 'benchmark', 'readers', spec['reader'] + '.py'))
        moved = end[m['moves']]
        for cell in m.get('workloads', cells):
            assert cell in cells
            assert cell in moved.get('workloads', cells), (m['name'], cell)


def test_bert_flops_against_one_hand_computed_layer():
    cfg, traffic = _load('configs', 'bert-base.json'), \
        _load('traffic', 'pretrain-seq128.json')
    # one token through one layer, two operations a multiply-add:
    # qkv 768x2304, scores and values over 128 keys, output 768x768,
    # feed-forward 768x3072 twice
    layer_token = (2 * 768 * 2304 + 2 * 128 * 768 + 2 * 128 * 768
                   + 2 * 768 * 768 + 2 * 2 * 768 * 3072)
    assert layer_token == 14_548_992
    heads = 2 * 768 * 768 + 2 * 2 * 768 \
        + 20 * (2 * 768 * 768 + 2 * 768 * 30522)
    forward = 12 * 128 * layer_token + heads
    assert bert_flops.forward_flops_per_sample(cfg, traffic) == forward
    assert bert_flops.train_flops_per_sample(cfg, traffic) == 3 * forward


def test_decoder_flops_and_bytes_against_hand_computed_step():
    cfg = _load('configs', 'gpt1-117m.json')
    matmul = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 40478
    assert lm_flops.matmul_params(cfg) == matmul == 116_021_760
    ops, byts = lm_flops.decode_step(cfg, active=128, live_kv_tokens=20000)
    assert ops == 2 * matmul * 128 + 4 * 768 * 12 * 20000
    # weights once in float32, and K and V of the live tokens: 73 728 B each
    assert byts == lm_flops.all_params(cfg) * 4 + 73728 * 20000
    assert lm_flops.token_flops(cfg, 100) == 2 * matmul + 4 * 768 * 100 * 12


def test_schedule_is_a_function_of_the_seed_with_one_fixed_multiset():
    traffic = _load('traffic', 'chat-steady.json')
    a = loadgen.open_loop_schedule(traffic, 1000, 7, 10.0)
    b = loadgen.open_loop_schedule(traffic, 1000, 7, 10.0)
    c = loadgen.open_loop_schedule(traffic, 1000, 2 ** 31 + 8, 10.0)
    key = lambda rs: [(r.due, r.prompt, r.max_new) for r in rs]  # noqa: E731
    assert key(a) == key(b) != key(c)
    ramp = traffic['ramp_seconds']
    gaps = lambda rs: sorted([rs[0].due + ramp] + [           # noqa: E731
        y.due - x.due for x, y in zip(rs, rs[1:])])
    assert len(a) == len(c) == round(traffic['rate_per_s']
                                     * (10.0 + traffic['ramp_seconds']))
    assert abs(a[-1].due - 10.0) < 2.0 / traffic['rate_per_s']
    assert a[0].due < 0 < a[-1].due       # the ramp comes before the window
    # another order of the same work: lengths over the whole population
    pa, pc = loadgen.population(traffic, 1000, 7), \
        loadgen.population(traffic, 1000, 99)
    assert sorted(len(p) for p, _ in pa) == sorted(len(p) for p, _ in pc)
    assert sorted(o for _, o in pa) == sorted(o for _, o in pc)
    assert gaps(a) == pytest.approx(gaps(c), abs=1e-9)
    lens = [len(p) for p, _ in pa]
    assert min(lens) >= 16 and max(lens) <= 384
    assert abs(sorted(lens)[len(lens) // 2] - 96) <= 2


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert loadgen.percentile(xs, 95) == 95
    assert loadgen.percentile([3.0], 95) == 3.0
    assert loadgen.percentile([], 95) is None


class _SlowStream(BaseHTTPRequestHandler):
    """Answers /generate with two token lines, 50 ms apart, after 100 ms."""
    protocol_version = 'HTTP/1.1'

    def do_POST(self):
        n = int(self.headers['Content-Length'])
        req = json.loads(self.rfile.read(n))
        time.sleep(0.1)
        self.send_response(200)
        self.send_header('Transfer-Encoding', 'chunked')
        self.end_headers()
        toks = list(range(req['max_new_tokens']))
        for i, t in enumerate(toks):
            self._chunk({'token': t, 'index': i})
            time.sleep(0.05)
        self._chunk({'done': True, 'tokens': toks, 'degraded': False})
        self.wfile.write(b'0\r\n\r\n')

    def _chunk(self, obj):
        data = json.dumps(obj).encode() + b'\n'
        self.wfile.write(b'%x\r\n' % len(data) + data + b'\r\n')
        self.wfile.flush()

    def log_message(self, *a):
        pass


def test_open_loop_times_from_the_due_time():
    srv = ThreadingHTTPServer(('127.0.0.1', 0), _SlowStream)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    try:
        traffic = {'loop': 'open', 'rate_per_s': 20.0, 'population': 8,
                   'ramp_seconds': 0.2, 'grace_seconds': 5.0,
                   'prompt_len': {'median': 4, 'sigma': 0.1, 'min': 2,
                                  'max': 8},
                   'output_len': {'median': 2, 'sigma': 0.01, 'min': 2,
                                  'max': 2}}
        reqs, t_open = loadgen.drive(srv.server_address[1], traffic, 50, 3,
                                     1.0)
    finally:
        srv.shutdown()
        srv.server_close()
        th.join(5)
    assert not th.is_alive()
    assert len(reqs) == 24 and all(r.error is None for r in reqs)
    measured = [r for r in reqs if r.measured]
    assert 15 <= len(measured) <= 24
    for r in measured:
        due = t_open + r.due
        assert r.sent >= due - 1e-3            # never sent early
        assert r.first - due >= 0.1            # the server's delay, from due
        assert r.first - due >= r.first - r.sent - 1e-9
        assert len(r.tokens) == 2 and r.last - r.first >= 0.04


def _planes():
    ms = 1_000_000
    ops = [(0, 10 * ms, '%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop'),
           (5 * ms, 20 * ms, '%all-reduce.1 = f32[8]{0} all-reduce(f32[8] %x)'),
           (40 * ms, 50 * ms, '%fusion.2 = f32[8]{0} fusion(f32[8] %p)')]
    return {'/device:TPU:0': {
                'XLA Ops': ops,
                'XLA Modules': [(0, 20 * ms, 'jit_step(123)'),
                                (40 * ms, 50 * ms, 'jit_step(123)')]},
            '/host:CPU': {'python3': [
                (18 * ms, 45 * ms, 'wait_loss'), (1 * ms, 60 * ms, 'step'),
                (20 * ms, 30 * ms, 'tpu::System::Execute')]}}


def test_reduction_on_hand_made_planes():
    r = xplane.reduce_planes(_planes(), 1)
    assert r['window_s'] == pytest.approx(0.050)
    assert r['busy_s'] == pytest.approx(0.030)       # union of [0,20],[40,50]
    assert r['idle_share'] == pytest.approx(0.4)
    assert r['collective_s'] == pytest.approx(0.015)
    assert r['collective_exposed_s'] == pytest.approx(0.010)  # [10,20] bare
    assert r['modules']['jit_step(123)'] == [pytest.approx(0.030), 2]
    # the one gap [20,40] goes to the innermost host span over its middle,
    # and the runtime's own C++ span is not a candidate
    assert r['idle_gaps'] == [['wait_loss', pytest.approx(0.020)]]
    assert r['device_ops'][0] == ['%all-reduce.1 f32[8] all-reduce',
                                  pytest.approx(0.015)]
    with pytest.raises(RuntimeError):
        xplane.reduce_planes({'/host:CPU': {}}, 1)


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert xplane.gaps([[2, 3]], 0, 5) == [[0, 2], [3, 5]]


def test_reduction_on_the_recorded_v5e_trace():
    path = os.path.join(REPO, 'benchmark', 'testdata', 'small_v5e.xplane.pb')
    r = xplane.reduce(path, 1)
    (name, calls), = r['modules'].items()
    assert name.startswith('jit_small_step(')
    assert calls[1] == 3            # five calls, the edge two left out
    from benchmark.readers import module_device_ms
    ms = module_device_ms.read({'xplane': r}, module='jit_small_step')
    assert ms == pytest.approx(1e3 * calls[0] / 3) and 0.01 < ms < 50
    assert 0 < r['busy_s'] < r['window_s'] < 1.0
    assert 0.0 < r['idle_share'] < 1.0
    assert r['collective_s'] == 0.0
    assert {n for n, _ in r['idle_gaps']} & {'host_sleep', 'step',
                                             'wait_loss'}
    assert r['device_ops'] and all(t > 0 for _, t in r['device_ops'])


def test_command_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, '-m', 'benchmark.run', '--workload',
         'bert-base-pretrain', '--seed', '1', '--seconds', '1', '--trace',
         '0'], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ''
    assert 'refused' in out.stderr


def test_a_config_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """A copy of the benchmark plus new files and new BENCHMARK.json
    entries, and no edit to any file that was there."""
    tree = bench_tiny.build(tmp_path / 'tree')
    shutil.copytree(os.path.join(REPO, 'benchmark'),
                    os.path.join(tree, 'benchmark'), dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns('__pycache__', 'testdata'))
    with open(os.path.join(tree, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    with open(os.path.join(tree, 'benchmark/configs/gpt-tiny.json')) as f:
        cfg = json.load(f)
    cfg['n_layer'] = 3
    with open(os.path.join(tree, 'benchmark/configs/gpt-new.json'), 'w') as f:
        json.dump(cfg, f)
    with open(os.path.join(tree, 'benchmark/traffic/sat-tiny.json')) as f:
        traffic = json.load(f)
    traffic['clients'] = 3
    with open(os.path.join(tree, 'benchmark/traffic/new-mix.json'), 'w') as f:
        json.dump(traffic, f)
    with open(os.path.join(tree, 'benchmark/limits/gpt-new-mix.json'),
              'w') as f:
        json.dump({'logit_gap_max': 1e-3}, f)
    with open(os.path.join(tree, 'benchmark/readers/requests_sent.py'),
              'w') as f:
        f.write('def read(facts, scale):\n'
                '    return scale * facts["attempted"]\n')
    with open(os.path.join(tree, 'benchmark/metrics/requests_sent.json'),
              'w') as f:
        json.dump({'reader': 'requests_sent', 'args': {'scale': 2}}, f)
    bench['configs'].append({'name': 'gpt-new', 'source': 'toy',
                             'file': 'benchmark/configs/gpt-new.json',
                             'reduced': [], 'why': 'toy'})
    bench['workloads'].append({'name': 'gpt-new-mix', 'config': 'gpt-new',
                               'traffic': 'new-mix', 'chips': 1,
                               'why': 'toy'})
    bench['per_layer'].append({
        'name': 'requests_sent', 'unit': 'count', 'better': 'higher',
        'source': 'program_counter', 'layer': 'load generator',
        'moves': 'serve_tokens_per_s', 'workloads': ['gpt-new-mix']})
    for m in bench['end_to_end']:
        if m['name'] == 'serve_tokens_per_s':
            m['workloads'].append('gpt-new-mix')
    with open(os.path.join(tree, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)
    code = ('import json\nfrom benchmark import run, entry\n'
            'from benchmark.entry import serve\n'
            'b = run.load_json(".", "BENCHMARK.json")\n'
            'ctx = run.Context(".", b, run.find_cell(b, "gpt-new-mix"), 3, '
            '1.0, 0, require_chip=False)\n'
            'import mxnet_tpu\nctx.attach_devices()\n'
            'facts = serve.run(ctx)\n'
            'facts["xplane"] = None\n'
            'm = run.read_layer_metrics(ctx, {k: v for k, v in facts.items()'
            ' if k != "xplane"} | {"xplane": {"idle_share": 0.5, '
            '"modules": {}}})\n'
            'print(json.dumps({"correct": facts["verdict"].correct, '
            '"attempted": facts["attempted"], "m": m}))\n')
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PYTHONPATH=tree + os.pathsep + REPO)
    out = subprocess.run([sys.executable, '-c', code], cwd=tree, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got['correct'] and got['attempted'] > 0
    assert got['m']['requests_sent']['value'] == 2 * got['attempted']
    assert 'slot_occupancy.serve' not in got['m']   # not one of its metrics
