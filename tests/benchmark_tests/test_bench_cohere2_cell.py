"""The ``cohere2_moe`` cell at a toy size on the CPU (``bench_tiny_cmda``):
the harness's own entry against the family's plain reference, the float8
control, and six planted faults that must each come out not correct.

The limit. Everything is float32 here, so the program reads the order of
its sums (0.0 on the seeds tried: no served token was ever below the
reference's best; the reference's near-tie rule leaves out 66-93 of some
540 positions). The limit, 2e-4, stands a decade below what bfloat16
operands read (1.5e-3) and far below the float8 control (0.21) and the
faults (window mask off 0.66, RoPE on the full layer 0.12, shared experts
summed 0.43, weights normalised over held experts only 0.22, routed experts
left out 0.40, every held expert computing its neighbour 0.59; seed 29)."""
import pytest

import bench_tiny
import bench_tiny_cmda
from benchmark import run
from benchmark.entry import serve
from mxnet_tpu.serving.decode import Cohere2MoELM

CELL = bench_tiny_cmda.CELL


@pytest.fixture(scope='module')
def tree(tmp_path_factory):
    return bench_tiny_cmda.build(tmp_path_factory.mktemp('bench_cmda'))


def test_cell_proves_correct_and_reports_its_metrics(tree):
    line = run.execute(CELL, 2 ** 31 + 17, 1.0, 0, require_chip=False,
                       root=tree)
    assert line['correct'], line['compared']
    assert line['failed'] == 0 and line['attempted'] > 0
    assert set(line['metrics']) == {'serve_tokens_per_s', 'setup_s'}
    assert line['compared']['tokens_compared']['value'] > 100
    assert line['compared']['logit_gap_max']['value'] < 1e-5


def test_float8_control_is_not_correct(tree):
    readings = serve.control(bench_tiny.context(tree, CELL, seed=13))
    assert readings['program']['logit_gap_max'] <= bench_tiny_cmda.LIMIT
    assert not readings['control_float8_e4m3fn']['correct'], readings


def _no_window(monkeypatch):
    real = Cohere2MoELM.__init__

    def init(self, config):
        real(self, dict(config, window=10 ** 6))
    monkeypatch.setattr(Cohere2MoELM, '__init__', init)


def _rope_everywhere(monkeypatch):
    real = Cohere2MoELM._qkv
    monkeypatch.setattr(
        Cohere2MoELM, '_qkv',
        lambda self, p, n, positions, sliding: real(self, p, n, positions,
                                                    True))


def _shared_summed(monkeypatch):
    real = Cohere2MoELM._shared
    monkeypatch.setattr(Cohere2MoELM, '_shared',
                        lambda self, p, n: real(self, p, n) * self.shared)


def _planted_in_weights(monkeypatch, change):
    """A fault in what the program multiplies by, planted where the chip
    runs plant it (the compiled programs stay what they were): the
    reference keeps the sound weights."""
    from benchmark.systems import cohere2_moe as systems
    real = systems.program_params

    def params(weights):
        p = real(weights)
        for name in list(p):
            change(p, name)
        return p
    monkeypatch.setattr(systems, 'program_params', params)


def _routed_left_out(monkeypatch):
    import jax.numpy as jnp

    def change(p, name):
        if name.endswith('_w2'):
            p[name] = jnp.zeros_like(p[name])
    _planted_in_weights(monkeypatch, change)


def _expert_shifted(monkeypatch):
    """Every held expert computes its neighbour's product."""
    import jax.numpy as jnp

    def change(p, name):
        if name.endswith(('_w1', '_w3', '_w2')):
            p[name] = jnp.roll(p[name], 1, axis=0)
    _planted_in_weights(monkeypatch, change)


def _normalised_over_held(monkeypatch):
    import jax.numpy as jnp
    real = Cohere2MoELM._route

    def route(self, p, n):
        weights, ids = real(self, p, n)
        mine = jnp.asarray(self._local_of)[ids] >= 0
        kept = jnp.where(mine, weights, 0.0)
        return kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-9), ids
    monkeypatch.setattr(Cohere2MoELM, '_route', route)


@pytest.mark.parametrize('plant', [_no_window, _rope_everywhere,
                                   _shared_summed, _normalised_over_held,
                                   _routed_left_out, _expert_shifted])
def test_planted_fault_is_not_correct(tree, monkeypatch, plant):
    plant(monkeypatch)
    facts = serve.run(bench_tiny.context(tree, CELL, seed=29))
    rows = facts['verdict'].rows
    assert not facts['verdict'].correct, rows
    assert not rows['logit_gap_max']['ok'], rows
    # the fault is in the mathematics, not in the serving
    assert rows['never_answered']['ok'] and rows['wrong_length']['ok']
