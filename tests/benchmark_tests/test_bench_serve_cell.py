"""The serving cells at a toy size on the CPU: real ``ServingHTTPServer``,
real load generator, plain reference; an altered token or a lower
precision makes ``correct`` false."""
import pytest

import bench_tiny
from benchmark import run
from benchmark.entry import serve


@pytest.fixture(scope='module')
def tree(tmp_path_factory):
    return bench_tiny.build(tmp_path_factory.mktemp('bench_serve'))


_context = bench_tiny.context


@pytest.mark.parametrize('cell,metrics', [
    ('gpt-tiny-sat', {'serve_tokens_per_s', 'setup_s'}),
    ('gpt-tiny-chat', {'ttft_p95_ms', 'tpot_p95_ms', 'setup_s'})])
def test_serve_cell_proves_correct(tree, cell, metrics):
    line = run.execute(cell, 2 ** 31 + 11, 1.0, 0, require_chip=False,
                       root=tree)
    assert line['correct'], line['compared']
    assert line['failed'] == 0 and line['attempted'] > 0
    assert set(line['metrics']) == metrics
    assert all(m['value'] > 0 for m in line['metrics'].values())
    assert line['compared']['tokens_compared']['value'] > 0


def test_altered_token_is_not_correct(tree, monkeypatch):
    from mxnet_tpu.serving.decode.engine import DecodeEngine
    real, calls = DecodeEngine._emit_token, [0]

    def altered(self, seq, tok):
        calls[0] += 1
        return real(self, seq, (tok + 1) % 97 if calls[0] % 5 == 0 else tok)

    monkeypatch.setattr(DecodeEngine, '_emit_token', altered)
    facts = serve.run(_context(tree, 'gpt-tiny-sat'))
    assert not facts['verdict'].correct
    assert not facts['verdict'].rows['logit_gap_max']['ok']


def test_control_precision_is_not_correct(tree):
    readings = serve.control(_context(tree, 'gpt-tiny-chat', seed=13))
    assert readings['program']['logit_gap_max'] <= 1e-3
    assert not readings['control_float8_e4m3fn']['correct'], readings
