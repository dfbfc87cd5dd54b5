"""Distributed request tracing (mxnet_tpu/observability/trace.py,
docs/OBSERVABILITY.md "Distributed request tracing"): the context /
header wire format, the bounded span buffer and its NDJSON drain, the
cross-process stitcher (orphans, torn lines, completeness verdicts),
per-hop clock-skew normalization, the TTFT critical-path split, the
off-path no-op contract — and, against fake NDJSON replicas, the
gateway propagating ONE trace_id across relay, failover resume and
the disaggregated prefill->decode handoff."""
import json
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from mxnet_tpu.observability import trace
from mxnet_tpu.serving.gateway import ServingGateway

# ---------------------------------------------------------------------------
# context + header wire format
# ---------------------------------------------------------------------------


def test_header_round_trip():
    ctx = trace.TraceContext.new()
    assert ctx.span_id is None and ctx.parent_id is None
    hdr = ctx.to_header()
    # W3C traceparent shape: version-trace-span-flags
    ver, tid, sid, flags = hdr.split('-')
    assert (ver, flags) == ('00', '01')
    assert tid == ctx.trace_id and len(tid) == 32
    assert sid == trace.NO_PARENT     # no span opened yet
    parsed = trace.parse_header(hdr)
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id is None     # all-zero = root-to-be

    child = parsed.child()
    assert child.trace_id == ctx.trace_id
    assert len(child.span_id) == 16 and child.parent_id is None
    hop = trace.parse_header(child.to_header())
    assert hop.trace_id == ctx.trace_id
    assert hop.span_id == child.span_id   # sender's span = my parent


@pytest.mark.parametrize('bad', [
    None, '', 'garbage', '00-abc', '00-%s-%s' % ('a' * 32, 'b' * 16),
    '00-zz-yy-01', '00-' + 'g' * 32 + '-' + 'b' * 16 + '-01'])
def test_malformed_header_is_none_not_an_error(bad):
    assert trace.parse_header(bad) is None


# ---------------------------------------------------------------------------
# span buffer
# ---------------------------------------------------------------------------


@pytest.fixture()
def traced():
    trace.set_enabled(True)
    yield
    trace.set_enabled(None)


def test_buffer_bounds_drop_oldest(traced):
    buf = trace.SpanBuffer(capacity=4, site='t')
    ctx = trace.TraceContext.new()
    for i in range(10):
        buf.emit('s%d' % i, ctx.child(), float(i), float(i) + 0.5)
    recs = buf.read()
    assert [r['name'] for r in recs] == ['s6', 's7', 's8', 's9']
    st = buf.stats()
    assert st['emitted'] == 10 and st['buffered'] == 4
    assert st['dropped'] == 6 and st['capacity'] == 4
    # the since cursor drains incrementally
    assert buf.read(since=recs[-2]['seq']) == recs[-1:]


def test_buffer_ndjson_round_trip_and_torn_line(traced):
    buf = trace.SpanBuffer(capacity=8, site='t')
    ctx = trace.TraceContext.new()
    buf.emit('a', ctx.child(), 1.0, 2.0, k='v')
    buf.emit('b', ctx.child(), 2.0, 3.0)
    payload = buf.ndjson()
    head = json.loads(payload.splitlines()[0])
    assert head['schema'] == trace.TRACE_SCHEMA
    assert head['count'] == 2 and head['cursor'] == 2
    recs = trace.read_ndjson(payload)
    assert [r['name'] for r in recs] == ['a', 'b']
    assert recs[0]['attrs'] == {'k': 'v'}
    # a torn tail line (crash mid-write) parses to what's intact
    torn = payload[:-20]
    assert [r['name'] for r in trace.read_ndjson(torn)] == ['a']
    # incremental scrape from the returned cursor is empty
    assert trace.read_ndjson(buf.ndjson(since=head['cursor'])) == []


def test_disabled_path_is_a_shared_noop():
    trace.set_enabled(False)
    try:
        buf = trace.SpanBuffer(capacity=8, site='t')
        ctx = trace.TraceContext.new()
        sp1 = buf.span('x', ctx)
        sp2 = buf.span('y', ctx.child())
        assert sp1 is sp2             # one shared null span, no alloc
        with sp1 as sp:
            assert sp.ctx is None     # children see None => no-ops
        assert buf.emit('z', ctx.child(), 0.0, 1.0) is None
        assert buf.read() == [] and buf.stats()['emitted'] == 0
    finally:
        trace.set_enabled(None)


def test_enabled_span_with_none_ctx_is_noop(traced):
    buf = trace.SpanBuffer(capacity=8, site='t')
    with buf.span('x', None) as sp:
        assert sp.ctx is None
    assert buf.emit('y', None, 0.0, 1.0) is None
    assert buf.read() == []


# ---------------------------------------------------------------------------
# stitcher + skew normalization + critical path (synthetic records)
# ---------------------------------------------------------------------------


def _rec(site, tid, span, parent, name, t0, t1):
    return {'site': site, 'trace': tid, 'span': span,
            'parent': parent, 'name': name, 't0': t0, 't1': t1}


def test_stitch_complete_tree_and_verdict():
    t = 'a' * 32
    recs = [_rec('gw', t, 's1', None, 'gw.request', 0.0, 1.0),
            _rec('gw', t, 's2', 's1', 'gw.relay', 0.1, 0.9),
            _rec('rep', t, 's3', 's2', 'srv.generate', 0.2, 0.8)]
    trees = trace.stitch(recs)
    tree = trees[t]
    assert tree['roots'] == ['s1'] and not tree['orphans']
    assert tree['children']['s1'] == ['s2']
    assert trace.tree_verdict(tree) is True


def test_stitch_orphan_and_multi_root_fail_verdict():
    t = 'b' * 32
    # parent s9 was never scraped -> s3 is an orphan
    trees = trace.stitch([
        _rec('gw', t, 's1', None, 'gw.request', 0.0, 1.0),
        _rec('rep', t, 's3', 's9', 'srv.generate', 0.2, 0.8)])
    tree = trees[t]
    assert tree['orphans'] == ['s3']
    assert trace.tree_verdict(tree) is False
    # two roots is torn too
    trees = trace.stitch([
        _rec('gw', t, 's1', None, 'gw.request', 0.0, 1.0),
        _rec('gw', t, 's2', None, 'gw.request', 2.0, 3.0)])
    assert trace.tree_verdict(trees[t]) is False


def test_normalize_skew_pulls_remote_site_into_root_timeline():
    t = 'c' * 32
    # replica clock is ~+100s ahead; its span must land inside the
    # gateway relay bounds after normalization
    recs = [_rec('gw', t, 's1', None, 'gw.request', 10.0, 11.0),
            _rec('gw', t, 's2', 's1', 'gw.relay', 10.1, 10.9),
            _rec('rep', t, 's3', 's2', 'srv.generate', 110.2, 110.8)]
    tree = trace.stitch(recs)[t]
    offsets = trace.normalize_skew(tree)
    assert offsets['gw'] == 0.0
    assert -100.2 < offsets['rep'] < -99.8
    child = tree['spans']['s3']
    parent = tree['spans']['s2']
    assert parent['t0'] <= child['t0'] <= child['t1'] <= parent['t1']
    # waterfall rows are root-relative and ordered by depth-first walk
    rows = trace.waterfall(tree)
    assert [r['name'] for r in rows] == ['gw.request', 'gw.relay',
                                        'srv.generate']
    assert rows[0]['start_ms'] == 0.0


def test_ttft_decomposition_and_critical_path():
    t = 'd' * 32
    recs = [_rec('gw', t, 's1', None, 'gw.request', 0.0, 2.0),
            _rec('gw', t, 's2', 's1', 'gw.relay', 0.0, 2.0),
            _rec('rep', t, 's3', 's2', 'eng.queue_wait', 0.0, 0.2),
            _rec('rep', t, 's4', 's2', 'eng.prefill', 0.2, 0.7),
            _rec('rep', t, 's5', 's2', 'eng.first_token', 0.7, 0.8),
            _rec('rep', t, 's6', 's2', 'eng.steps', 0.8, 1.8)]
    recs[-1]['attrs'] = {'tokens': 10}
    tree = trace.stitch(recs)[t]
    ttft, parts = trace.decompose_ttft(tree)
    assert abs(ttft - 0.8) < 1e-6
    assert abs(parts['queue'] - 0.2) < 1e-6
    assert abs(parts['prefill'] - 0.5) < 1e-6
    assert parts['handoff'] == 0.0
    cp = trace.critical_path([tree])
    assert cp['n'] == 1
    assert abs(cp['ttft']['p50']['ttft_ms'] - 800.0) < 1e-3
    shares = cp['ttft']['p50']['share_pct']
    assert shares['prefill'] > shares['queue'] > 0


# ---------------------------------------------------------------------------
# gateway propagation against fake NDJSON replicas
# ---------------------------------------------------------------------------


def _next_tok(seq):
    return (seq[-1] * 31 + 17) % 997


class _Handler(BaseHTTPRequestHandler):
    protocol_version = 'HTTP/1.1'

    def log_message(self, *args):
        pass

    def _chunk(self, obj):
        line = (json.dumps(obj) + '\n').encode()
        self.wfile.write(b'%x\r\n' % len(line))
        self.wfile.write(line + b'\r\n')
        self.wfile.flush()

    def _end_chunks(self):
        self.wfile.write(b'0\r\n\r\n')
        self.wfile.flush()

    def do_GET(self):
        body = json.dumps(
            {'ok': True,
             'decode': {'pages': {'occupancy_pct': 0.0}}}).encode()
        self.send_response(200)
        self.send_header('Content-Type', 'application/json')
        self.send_header('Content-Length', str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):
        ctl = self.server.ctl
        length = int(self.headers.get('Content-Length', 0) or 0)
        req = json.loads(self.rfile.read(length) or b'{}')
        ctl['hits'].append(
            {'path': self.path.split('?')[0].rstrip('/'),
             'trace': self.headers.get(trace.TRACE_HEADER),
             'body': req})
        self.send_response(200)
        self.send_header('Content-Type', 'application/x-ndjson')
        self.send_header('Transfer-Encoding', 'chunked')
        self.end_headers()
        if self.path.split('?')[0].rstrip('/') == '/import':
            state = req['seqstate']
            seq = ([int(x) for x in state['tokens']]
                   + [int(x) for x in state['emitted']])
            n = int(state['max_new_tokens']) - len(state['emitted'])
            start = int(req.get('start_index')
                        if req.get('start_index') is not None
                        else len(state['emitted']))
            for i in range(n):
                tok = _next_tok(seq)
                seq.append(tok)
                self._chunk({'token': tok, 'index': start + i})
            done = {'done': True, 'finish_reason': 'length'}
            if state.get('request_id') is not None:
                done['request_id'] = state['request_id']
            self._chunk(done)
            self._end_chunks()
            return
        seq = [int(x) for x in req['tokens']]
        n = int(req.get('max_new_tokens', 8))
        start = int(req.get('start_index', 0) or 0)
        if req.get('prefill_only'):
            tok = _next_tok(seq)
            self._chunk({'token': tok, 'index': start})
            self._chunk({'done': True, 'finish_reason': 'migrated',
                         'seqstate': {'kind': 'fake',
                                      'tokens': seq, 'emitted': [tok],
                                      'max_new_tokens': n,
                                      'request_id':
                                          req.get('request_id')}})
            self._end_chunks()
            return
        die_after = ctl.pop('die_after', None)
        for i in range(n):
            tok = _next_tok(seq)
            seq.append(tok)
            self._chunk({'token': tok, 'index': start + i})
            if die_after is not None and i + 1 >= die_after:
                self.close_connection = True   # transport death
                return
        self._chunk({'done': True, 'finish_reason': 'length'})
        self._end_chunks()


class _Server(ThreadingHTTPServer):
    daemon_threads = True


class _Fake:
    def __init__(self):
        self.ctl = {'hits': []}
        self._httpd = _Server(('127.0.0.1', 0), _Handler)
        self._httpd.ctl = self.ctl
        self.port = self._httpd.server_address[1]
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        return 'http://127.0.0.1:%d' % self.port

    def trace_ids(self, path=None):
        return [trace.parse_header(h['trace']).trace_id
                for h in self.ctl['hits']
                if h['trace'] and (path is None or h['path'] == path)]

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()


def _stream(port, payload, header=None, timeout=10.0):
    body = json.dumps(payload).encode()
    hdrs = {'Content-Type': 'application/json'}
    if header:
        hdrs[trace.TRACE_HEADER] = header
    req = urllib.request.Request(
        'http://127.0.0.1:%d/generate' % port, data=body,
        headers=hdrs)
    tokens, done = [], None
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for line in resp:
            obj = json.loads(line)
            if 'token' in obj:
                tokens.append(obj['token'])
            elif obj.get('done'):
                done = obj
    return tokens, done


_PROMPT = [5, 11, 7, 2]


def _drain_gateway_spans(gw, want, timeout=5.0):
    """The client resolves on the done LINE while the handler thread
    is still closing its spans — poll until `want` names appear."""
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        recs = gw._trace_buf.read()
        if want <= {r['name'] for r in recs}:
            return recs
        time.sleep(0.02)
    return gw._trace_buf.read()


@pytest.fixture()
def fake_pair(traced):
    a, b = _Fake(), _Fake()
    gw = ServingGateway([a.url, b.url], port=0, health_period_s=30.0,
                        timeout_s=5.0, resume=True, resume_max=2,
                        affinity=True).start()
    yield gw, {a.url: a, b.url: b}
    gw.stop()
    a.close()
    b.close()


def test_gateway_propagates_trace_and_emits_request_tree(fake_pair):
    gw, by_url = fake_pair
    ctx = trace.TraceContext.new()
    tokens, done = _stream(gw.port, {'tokens': _PROMPT,
                                     'max_new_tokens': 6,
                                     'stream': True},
                           header=ctx.to_header())
    assert len(tokens) == 6 and done['finish_reason'] == 'length'
    seen = [tid for rep in by_url.values() for tid in rep.trace_ids()]
    assert seen == [ctx.trace_id]     # one replica hop, same trace
    recs = _drain_gateway_spans(gw, {'gw.request', 'gw.relay'})
    by_name = {}
    for r in recs:
        if r['trace'] == ctx.trace_id:
            by_name.setdefault(r['name'], []).append(r)
    # (no tenant admission configured => no gw.admit span)
    assert set(by_name) >= {'gw.request', 'gw.route', 'gw.relay'}
    root = by_name['gw.request'][0]
    assert root['parent'] is None
    tree = trace.stitch(
        [r for r in recs if r['trace'] == ctx.trace_id])[ctx.trace_id]
    assert trace.tree_verdict(tree) is True


def test_failover_resume_propagates_same_trace_id(fake_pair):
    gw, by_url = fake_pair
    target_url = gw.affinity_target(_PROMPT)
    target = by_url[target_url]
    survivor = next(r for u, r in by_url.items() if u != target_url)
    target.ctl['die_after'] = 3
    ctx = trace.TraceContext.new()
    tokens, done = _stream(gw.port, {'tokens': _PROMPT,
                                     'max_new_tokens': 8,
                                     'stream': True},
                           header=ctx.to_header())
    assert len(tokens) == 8 and done['resumed'] == 1
    # both hops — the killed first attempt and the resume — carried
    # the SAME trace id
    assert target.trace_ids() == [ctx.trace_id]
    assert survivor.trace_ids() == [ctx.trace_id]
    recs = _drain_gateway_spans(gw, {'gw.request', 'gw.readmit'})
    mine = [r for r in recs if r['trace'] == ctx.trace_id]
    names = [r['name'] for r in mine]
    assert names.count('gw.relay') == 2   # dead segment + resume
    assert 'gw.readmit' in names
    readmit = next(r for r in mine if r['name'] == 'gw.readmit')
    assert readmit['attrs']['cause'] == 'transport'
    assert trace.tree_verdict(
        trace.stitch(mine)[ctx.trace_id]) is True


def test_disagg_handoff_propagates_same_trace_id(traced):
    reps = [_Fake() for _ in range(4)]
    classes = ('prefill', 'prefill', 'decode', 'decode')
    gw = ServingGateway(
        [(r.url, c) for r, c in zip(reps, classes)], port=0,
        health_period_s=30.0, timeout_s=5.0, resume=True,
        resume_max=2, affinity=True, handoff_timeout_s=5.0,
        handoff_retries=2).start()
    try:
        ctx = trace.TraceContext.new()
        tokens, done = _stream(gw.port, {'tokens': _PROMPT,
                                         'max_new_tokens': 6,
                                         'stream': True},
                               header=ctx.to_header())
        assert len(tokens) == 6
        assert done['finish_reason'] == 'length'
        prefill_ids = [t for r in reps[:2]
                       for t in r.trace_ids('/generate')]
        import_ids = [t for r in reps[2:]
                      for t in r.trace_ids('/import')]
        # the prefill admission AND the decode-side import both rode
        # the client's trace
        assert prefill_ids == [ctx.trace_id]
        assert import_ids == [ctx.trace_id]
        recs = _drain_gateway_spans(gw, {'gw.request', 'gw.splice'})
        mine = [r for r in recs if r['trace'] == ctx.trace_id]
        names = {r['name'] for r in mine}
        assert {'gw.handoff', 'gw.splice'} <= names
        assert trace.tree_verdict(
            trace.stitch(mine)[ctx.trace_id]) is True
    finally:
        gw.stop()
        for r in reps:
            r.close()


def test_gateway_trace_endpoint_drains_with_cursor(fake_pair):
    gw, _ = fake_pair
    ctx = trace.TraceContext.new()
    _stream(gw.port, {'tokens': _PROMPT, 'max_new_tokens': 4,
                      'stream': True}, header=ctx.to_header())
    _drain_gateway_spans(gw, {'gw.request'})
    with urllib.request.urlopen(
            'http://127.0.0.1:%d/trace' % gw.port, timeout=5) as resp:
        payload = resp.read()
    head = json.loads(payload.splitlines()[0])
    assert head['schema'] == trace.TRACE_SCHEMA
    assert head['site'] == 'gateway' and head['count'] >= 3
    recs = trace.read_ndjson(payload)
    assert {r['name'] for r in recs} >= {'gw.request', 'gw.relay'}
    with urllib.request.urlopen(
            'http://127.0.0.1:%d/trace?since=%d'
            % (gw.port, head['cursor']), timeout=5) as resp:
        again = json.loads(resp.read().splitlines()[0])
    assert again['count'] == 0


def test_tracing_off_forwards_nothing_and_streams_identically():
    a, b = _Fake(), _Fake()
    gw = ServingGateway([a.url, b.url], port=0, health_period_s=30.0,
                        timeout_s=5.0, resume=True,
                        affinity=True).start()
    try:
        assert not trace.enabled()
        ctx = trace.TraceContext.new()
        with_hdr, done1 = _stream(gw.port,
                                  {'tokens': _PROMPT,
                                   'max_new_tokens': 6,
                                   'stream': True},
                                  header=ctx.to_header())
        without, done2 = _stream(gw.port,
                                 {'tokens': _PROMPT,
                                  'max_new_tokens': 6,
                                  'stream': True})
        assert with_hdr == without    # bit-identical token stream
        assert done1['finish_reason'] == done2['finish_reason']
        # no header forwarded, no spans buffered
        hits = a.ctl['hits'] + b.ctl['hits']
        assert all(h['trace'] is None for h in hits
                   if h['path'] == '/generate')
        assert gw._trace_buf.read() == []
    finally:
        gw.stop()
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# a real replica: a request without a parent gets a root span
# ---------------------------------------------------------------------------


@pytest.mark.parametrize('header,parent', [
    ('00-' + 'ab' * 16 + '-' + 'cd' * 8 + '-01', 'cd' * 8),
    ('00-' + 'ab' * 16 + '-' + '0' * 16 + '-01', None),
    (None, None),
    ('garbage', None),
])
def test_inbound_context_parent_or_fresh_root(traced, header, parent):
    headers = {} if header is None else {trace.TRACE_HEADER: header}
    ctx = trace.inbound(headers)
    assert ctx.span_id == parent and ctx.parent_id is None
    if header and header != 'garbage':
        assert ctx.trace_id == 'ab' * 16
    else:       # nothing usable arrived: an identity of its own
        assert len(ctx.trace_id) == 32
        assert ctx.trace_id != trace.inbound(headers).trace_id


def test_inbound_is_none_and_reads_no_header_when_tracing_is_off():
    class Untouchable:
        def get(self, key):
            raise AssertionError('header read with tracing off')
    assert not trace.enabled()
    assert trace.inbound(Untouchable()) is None


@pytest.fixture(scope='module')
def replica():
    """A toy paged decoder behind the real HTTP server."""
    from mxnet_tpu import serving
    from mxnet_tpu.serving.decode import (PagedDecodeProgram,
                                          init_transformer_lm)
    from mxnet_tpu.serving.server import ServingHTTPServer
    model, params = init_transformer_lm(vocab=23, units=16, hidden=24,
                                        layers=2, heads=4, max_len=48,
                                        seed=0)
    prog = PagedDecodeProgram(model, params, slots=4,
                              prefill_buckets=(4, 8), page_size=8)
    sess = serving.InferenceSession(prog, watchdog=False)
    srv = ServingHTTPServer(sess, 0).start()
    yield srv
    srv.stop()
    sess.close()


def _replica_spans(srv, want, timeout=5.0):
    """``srv.generate`` closes after the client has its last line."""
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        recs = srv._trace_buf.read()
        if sum(r['name'] == 'srv.generate' for r in recs) >= want:
            return recs
        time.sleep(0.02)
    return srv._trace_buf.read()


def test_headerless_generate_is_one_rooted_trace_a_request(replica,
                                                            traced):
    replica._trace_buf.clear()
    for prompt in ([5, 11, 7], [3, 1, 4, 1, 5]):
        tokens, done = _stream(replica.port,
                               {'tokens': prompt, 'max_new_tokens': 4,
                                'stream': True})
        assert len(tokens) == 4 and done['finish_reason'] == 'length'
    trees = trace.stitch(_replica_spans(replica, 2))
    assert len(trees) == 2                  # one trace a request
    for tree in trees.values():
        assert trace.tree_verdict(tree) is True
        by_name = {r['name']: r for r in tree['spans'].values()}
        assert set(by_name) >= {'srv.generate', 'eng.queue_wait',
                                'eng.prefill', 'eng.first_token',
                                'eng.steps'}
        root = by_name['srv.generate']
        assert root['parent'] is None
        assert tree['roots'] == [root['span']]
        # the tick that admitted it: what the request's spans share
        # with the eng.tick span on the profiler's clock
        tick = by_name['eng.queue_wait']['attrs']['tick']
        assert isinstance(tick, int) and tick >= 0
        assert by_name['eng.prefill']['attrs']['tick'] == tick


def test_headerless_generate_with_tracing_off_builds_nothing(
        replica, monkeypatch):
    assert not trace.enabled()
    replica._trace_buf.clear()
    built = []
    real = trace.TraceContext.__init__

    def counting(self, *a, **kw):
        built.append(a)
        real(self, *a, **kw)
    monkeypatch.setattr(trace.TraceContext, '__init__', counting)
    tokens, done = _stream(replica.port,
                           {'tokens': [5, 11, 7], 'max_new_tokens': 3,
                            'stream': True})
    assert len(tokens) == 3 and done['finish_reason'] == 'length'
    assert built == []
    assert replica._trace_buf.read() == []
