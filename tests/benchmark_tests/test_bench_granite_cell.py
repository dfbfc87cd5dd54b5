"""The ``granitemoehybrid`` cell at a toy size on the CPU
(``bench_tiny_g4h``): the harness's own entry against the family's plain
reference, the float8 control, and six planted faults that must each come
out not correct; and the family's operations and bytes against a
hand-computed Mamba layer and a hand-computed step.

The limit. Everything is float32 here, so the program reads the order of
its sums (0.0 on the seeds tried: no served token was ever below the
reference's best; logits are of order 0.01). The limit, 2e-5, stands below
what bfloat16 operands read (1.2e-4), far below the float8 control (5.8e-3)
and below every fault (seed 29: the state carried in bfloat16 2.2e-4, pad
positions advancing the state 4.2e-3, the residual multiplier left out
8.9e-3, 1/sqrt(d) for the attention scale 5.2e-3, routed experts left out
1.2e-2, gates normalised over held experts only 1.2e-2). A state in
bfloat16 is caught here because nothing else rounds; on the chip it lies
inside what bfloat16 operands read (PERF.md 2)."""
import json
import os

import pytest

import bench_tiny
import bench_tiny_g4h
from benchmark import run
from benchmark.entry import serve
from benchmark.flops import granitemoehybrid as flops
from mxnet_tpu.serving.decode import GraniteHybridLM

CELL = bench_tiny_g4h.CELL


@pytest.fixture(scope='module')
def tree(tmp_path_factory):
    return bench_tiny_g4h.build(tmp_path_factory.mktemp('bench_g4h'))


def test_cell_proves_correct_and_reports_its_metrics(tree):
    line = run.execute(CELL, 2 ** 31 + 17, 1.0, 0, require_chip=False,
                       root=tree)
    assert line['correct'], line['compared']
    assert line['failed'] == 0 and line['attempted'] > 0
    assert set(line['metrics']) == {'serve_tokens_per_s', 'setup_s'}
    assert line['compared']['tokens_compared']['value'] > 100
    assert line['compared']['logit_gap_max']['value'] < 1e-7


def test_float8_control_is_not_correct(tree):
    readings = serve.control(bench_tiny.context(tree, CELL, seed=13))
    assert readings['program']['logit_gap_max'] <= bench_tiny_g4h.LIMIT
    assert not readings['control_float8_e4m3fn']['correct'], readings
    assert not readings['control_bfloat16']['correct'], readings


def _state_in_bfloat16(monkeypatch):
    real = GraniteHybridLM._mamba_step

    def step(self, p, r, live, state, conv_state):
        out, state, conv_state = real(self, p, r, live, state, conv_state)
        return out, state.astype('bfloat16').astype('float32'), conv_state
    monkeypatch.setattr(GraniteHybridLM, '_mamba_step', step)


def _padding_advances_the_state(monkeypatch):
    real = GraniteHybridLM._dt
    monkeypatch.setattr(
        GraniteHybridLM, '_dt',
        lambda self, p, dt_raw, real_rows: real(self, p, dt_raw,
                                                real_rows | True))


def _planted_in_weights(monkeypatch, change):
    """A fault in what the program multiplies by, planted where the chip
    runs plant it (the compiled programs stay what they were): the
    reference keeps the sound weights."""
    from benchmark.systems import granitemoehybrid as systems
    real = systems.program_params

    def params(weights):
        p = real(weights)
        for name in list(p):
            change(p, name)
        return p
    monkeypatch.setattr(systems, 'program_params', params)


def _residual_multiplier_left_out(monkeypatch):
    """Every branch output at 1 / 0.22 of itself is the block without its
    residual multiplier."""
    def change(p, name):
        if name.endswith(('_out_w', '_o_w', '_w2', '_s2')):
            p[name] = p[name] * (1.0 / 0.22)
    _planted_in_weights(monkeypatch, change)


def _attention_scaled_by_sqrt_d(monkeypatch):
    """Wq at multiplier^-1 / sqrt(d) of itself is 1 / sqrt(d) in the
    place of the attention multiplier."""
    cfg = bench_tiny_g4h.config()
    ratio = cfg['head_dim'] ** -0.5 / cfg['attention_multiplier']

    def change(p, name):
        if name.endswith('_q_w'):
            p[name] = p[name] * ratio
    _planted_in_weights(monkeypatch, change)


def _routed_left_out(monkeypatch):
    import jax.numpy as jnp

    def change(p, name):
        if name.endswith('_w2'):
            p[name] = jnp.zeros_like(p[name])
    _planted_in_weights(monkeypatch, change)


def _normalised_over_held(monkeypatch):
    import jax.numpy as jnp
    real = GraniteHybridLM._route

    def route(self, p, n):
        gate, ids = real(self, p, n)
        mine = jnp.asarray(self._experts.local_of)[ids] >= 0
        kept = jnp.where(mine, gate, 0.0)
        return kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-9), ids
    monkeypatch.setattr(GraniteHybridLM, '_route', route)


@pytest.mark.parametrize('plant', [
    _state_in_bfloat16, _padding_advances_the_state,
    _residual_multiplier_left_out, _attention_scaled_by_sqrt_d,
    _routed_left_out, _normalised_over_held])
def test_planted_fault_is_not_correct(tree, monkeypatch, plant):
    plant(monkeypatch)
    facts = serve.run(bench_tiny.context(tree, CELL, seed=29))
    rows = facts['verdict'].rows
    assert not facts['verdict'].correct, rows
    assert not rows['logit_gap_max']['ok'], rows
    # the fault is in the mathematics, not in the serving
    assert rows['never_answered']['ok'] and rows['wrong_length']['ok']


def _published():
    with open(os.path.join(bench_tiny.REPO, 'benchmark', 'configs',
                           'granite-4.0-h-small-10l-1of2.json')) as f:
        return json.load(f)


def test_flops_against_a_hand_computed_mamba_layer():
    cfg = _published()
    h, inner, n = 4096, 128 * 64, 128
    in_proj = h * (2 * inner + 2 * n + 128)          # z, x, B, C, dt
    assert in_proj == 68_681_728
    conv = (inner + 2 * n) * (4 + 1)                 # four taps and a bias
    mixer = in_proj + conv + 3 * 128 + inner + inner * h
    assert flops.mamba_mixer_params(cfg) == mixer == 102_286_976
    # a token: both projections, four taps a channel, and three
    # multiply-adds an element of the 128 x 64 x 128 state
    state = 128 * 64 * 128
    assert flops.mamba_mixer_flops(cfg) == \
        2 * in_proj + 2 * (inner + 2 * n) * 4 + 6 * state + 2 * inner * h
    # the second half of a layer: router, shared MLP, 36 held experts
    expert = 3 * h * 768
    assert expert == 9_437_184
    assert flops.mlp_params(cfg) == \
        h * 72 + 3 * h * 1536 + 36 * expert + 2 * h
    # a token meets 10 x 36 / 72 = 5 held experts on average
    assert flops.mlp_flops(cfg) == 2 * (h * 72 + 3 * h * 1536 + 5 * expert)
    assert flops.attention_params(cfg) == 2 * h * 4096 + 2 * h * 1024
    # a sequence's state: nine layers of 4 MiB float32 and three
    # bfloat16 rows of the convolution's 8448 channels
    assert flops.state_bytes(cfg) == 9 * (state * 4 + 3 * 8448 * 2) \
        == 38_204_928


def test_flops_and_bytes_against_a_hand_computed_step():
    cfg = _published()
    h, vocab = 4096, 100352
    layers = 9 * 102_286_976 + 41_943_040 + 10 * flops.mlp_params(cfg)
    assert layers == 4_551_686_784                   # ISSUE: 4 551.6 M
    # 64 sequences of 700 cached tokens on the one attention layer
    ops, byts = flops.decode_step(cfg, 64, 64 * 700)
    per_token = 9 * flops.mamba_mixer_flops(cfg) + 2 * 41_943_040 \
        + 10 * flops.mlp_flops(cfg)
    assert flops.token_flops(cfg) == per_token
    assert ops == 64 * (per_token + 2 * h * vocab) + 4 * 4096 * 64 * 700
    weights = 2 * (layers + h * vocab + h)
    assert byts == weights + 2 * 64 * 38_204_928 + 2 * 1024 * 64 * 700 * 2
    # ISSUE: 9.93 GB of weights, 2 x 2.44 GB of state, about 0.2 GB of K/V
    assert round(weights / 1e9, 2) == 9.93
    assert round(64 * 38_204_928 / 1e9, 2) == 2.45
    # the step is bound by bytes: its least time on a v5e is 18.4 ms
    peaks = run.load_json(bench_tiny.REPO, 'benchmark', 'peaks.json')[
        'TPU v5 lite']
    assert byts / peaks['hbm_bytes_per_s'] > ops / peaks['bf16_flops_per_s']
    assert 18.0 < 1e3 * byts / peaks['hbm_bytes_per_s'] < 18.8
    # the serve path: never more than every position through everything
    traffic = run.load_json(bench_tiny.REPO, 'benchmark', 'traffic',
                            'reasoning-saturated.json')
    per_out = flops.serve_flops_per_token(cfg, traffic)
    assert per_token + 2 * h * vocab < per_out < \
        2.0 * (per_token + 2 * h * vocab)
