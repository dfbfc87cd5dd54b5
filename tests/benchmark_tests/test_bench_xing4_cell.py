"""The ``xing4_0`` cell at a toy size on the CPU (``bench_tiny_x4``): the
harness's own entry against the family's plain reference, the float8
control, and ten planted faults that must each come out not correct; the
family's operations and bytes against a hand-computed layer, a hand-computed
step and a hand-computed latent attention; the new reader on canned facts.

The limit. Everything is float32 here, so the program reads the order of its
sums (0.0 on the seeds tried: no served token was ever below the reference's
best; logits are of order 3). The limit, 2e-4, stands below what bfloat16
operands read and far below the float8 control and every fault (the
readings are in the parametrised test's ids' order, in PERF.md 2)."""
import json
import os

import pytest

import bench_tiny
import bench_tiny_x4
from benchmark import run
from benchmark.entry import serve
from benchmark.flops import xing4_0 as flops
from benchmark.readers import kernel_roofline
from mxnet_tpu.serving.decode.xing4 import Xing4LM

CELL = bench_tiny_x4.CELL


@pytest.fixture(scope='module')
def tree(tmp_path_factory):
    return bench_tiny_x4.build(tmp_path_factory.mktemp('bench_x4'))


def test_cell_proves_correct_and_reports_its_metrics(tree):
    line = run.execute(CELL, 2 ** 31 + 17, 1.0, 0, require_chip=False,
                       root=tree)
    assert line['correct'], line['compared']
    assert line['failed'] == 0 and line['attempted'] > 0
    assert set(line['metrics']) == {'serve_tokens_per_s', 'setup_s'}
    assert line['compared']['tokens_compared']['value'] > 100
    assert line['compared']['logit_gap_max']['value'] < 1e-5


def test_every_admission_of_the_window_is_a_hit_on_latent_pages(tree):
    """Eight documents asked in turn by six clients: after the ramp's one
    prefill a document, requests are prefix hits whose last prompt token
    goes through the step."""
    facts = serve.run(bench_tiny.context(tree, CELL, seed=7, seconds=2.0))
    assert facts['verdict'].correct, facts['verdict'].rows
    assert facts['engine_prefills'] == 0
    assert facts['steps'] > 0 and facts['engine_tokens'] > 0


def test_float8_control_is_not_correct(tree):
    readings = serve.control(bench_tiny.context(tree, CELL, seed=13))
    assert readings['program']['logit_gap_max'] <= bench_tiny_x4.LIMIT
    assert not readings['control_float8_e4m3fn']['correct'], readings
    assert not readings['control_bfloat16']['correct'], readings


def _planted_in_weights(monkeypatch, change):
    """A fault in what the program multiplies by, planted where the chip
    runs plant it (the compiled programs stay what they were): the
    reference keeps the sound weights."""
    from benchmark.systems import xing4_0 as systems
    real = systems.program_params

    def params(weights):
        p = real(weights)
        for name in list(p):
            change(p, name)
        return p
    monkeypatch.setattr(systems, 'program_params', params)


def _latent_cached_without_its_norm(monkeypatch):
    real = Xing4LM._rms

    def rms(self, x, g):
        if g.shape == (self.kv_rank,):
            return x.astype('float32')
        return real(self, x, g)
    monkeypatch.setattr(Xing4LM, '_rms', rms)


def _plain_rope_for_yarn(monkeypatch):
    real = Xing4LM.__init__

    def init(self, config):
        sound = Xing4LM.__new__(Xing4LM)
        real(sound, config)
        real(self, dict(config, yarn=dict(config['yarn'], factor=1.0)))
        self.score_scale = sound.score_scale     # the fault is the angles'
    monkeypatch.setattr(Xing4LM, '__init__', init)


def _score_scale_without_mscale(monkeypatch):
    real = Xing4LM.__init__

    def init(self, config):
        real(self, config)
        self.score_scale = (self.nope + self.rope) ** -0.5
    monkeypatch.setattr(Xing4LM, '__init__', init)


def _one_sinkhorn_iteration(monkeypatch):
    real = Xing4LM.__init__

    def init(self, config):
        real(self, config)
        self.hc_iters = 1
    monkeypatch.setattr(Xing4LM, '__init__', init)


def _hpost_without_its_factor(monkeypatch):
    """Every branch output at half of itself is Hpost without its 2."""
    def change(p, name):
        if name.endswith(('_o_w', '_d2', '_w2', '_s2')):
            p[name] = p[name] * 0.5
    _planted_in_weights(monkeypatch, change)


def _selection_without_the_bias(monkeypatch):
    import jax.numpy as jnp

    def change(p, name):
        if name.endswith('_router_b'):
            p[name] = jnp.zeros_like(p[name])
    _planted_in_weights(monkeypatch, change)


def _gates_not_renormalised(monkeypatch):
    import jax
    import jax.numpy as jnp

    def route(self, p, h):
        scores = jax.nn.sigmoid(jnp.einsum(
            'th,he->te', h.astype('float32'),
            p('router_w').astype('float32'),
            precision=jax.lax.Precision.HIGHEST))
        _, top_i = jax.lax.top_k(scores + p('router_b'), self.top_k)
        return self.routed_scale * jnp.take_along_axis(scores, top_i, 1), \
            top_i
    monkeypatch.setattr(Xing4LM, '_route', route)


def _routed_factor_left_out(monkeypatch):
    def change(p, name):
        if name.endswith('_w2'):
            p[name] = p[name] * 0.5
    _planted_in_weights(monkeypatch, change)


def _routed_left_out(monkeypatch):
    import jax.numpy as jnp

    def change(p, name):
        if name.endswith('_w2'):
            p[name] = jnp.zeros_like(p[name])
    _planted_in_weights(monkeypatch, change)


def _values_from_the_wrong_columns(monkeypatch):
    """The step's values cut out of the row ``rope`` columns late: the
    latent's tail and the roped key, where the latent belongs. On both
    of the step's paths: the gathered view's slice, and the walk's
    column range (the context over the whole row, cut late)."""
    from mxnet_tpu.ops import pallas
    monkeypatch.setattr(
        Xing4LM, '_latent_values',
        lambda self, rows: rows[..., self.rope:self.rope + self.kv_rank])
    real = pallas.flash_paged_decode_attention
    late = 64                              # the served rope width

    def walk(q, pool, none, tables, positions, heads, scale, value_cols):
        width = pool.shape[-1]
        ctx = real(q, pool, none, tables, positions, heads=heads,
                   scale=scale, value_cols=width)
        return ctx.reshape(-1, heads, width)[
            ..., late:late + value_cols].reshape(ctx.shape[0], -1)
    monkeypatch.setattr(pallas, 'flash_paged_decode_attention', walk)


@pytest.mark.parametrize('plant', [
    _latent_cached_without_its_norm, _plain_rope_for_yarn,
    _score_scale_without_mscale, _one_sinkhorn_iteration,
    _hpost_without_its_factor, _selection_without_the_bias,
    _gates_not_renormalised, _routed_factor_left_out, _routed_left_out,
    _values_from_the_wrong_columns])
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
                           'xing4.0-29b-a4b-10l-1of4.json')) as f:
        return json.load(f)


def test_flops_against_a_hand_computed_layer():
    cfg = _published()
    c = 3584
    q_a, q_b = c * 768, 768 * 32 * 192
    kv_a, kv_b, o = c * 576, 512 * 32 * 256, 32 * 128 * c
    assert (q_a, q_b, kv_a, kv_b, o) == (2_752_512, 4_718_592, 2_064_384,
                                         4_194_304, 14_680_064)
    attention = q_a + q_b + kv_a + kv_b + o
    assert flops.attention_params(cfg) == attention == 28_409_856
    # a hyper-connection: 14336 x 24 of phi, the norm over the four
    # streams, three scales and 24 biases
    hc = 4 * c * 24 + 4 * c + 3 + 24
    assert flops.hyper_connection_params(cfg) == hc == 358_427
    expert = 3 * c * 1024
    assert flops.expert_params(cfg) == expert == 11_010_048
    common = attention + 768 + 512 + 2 * hc + 2 * c
    assert flops.layer_params(cfg, True) == common + 3 * c * 9216 \
        == 128_225_590
    assert flops.layer_params(cfg, False) == \
        common + c * 64 + 64 + 17 * expert == 216_535_414
    # a token: every attention weight once (the absorbed products are as
    # many multiply-adds as Wkvb has weights), two hyper-connections, and
    # the router, the shared expert and 4 x 16 / 64 = 1 routed expert
    hc_ops = 2 * 4 * c * 24 + 2 * c * (2 * 4 + 16)
    assert flops.hyper_connection_flops(cfg) == hc_ops
    assert flops.layer_flops(cfg, False) == \
        2 * attention + 2 * hc_ops + 2 * (c * 64 + 2 * expert)
    assert flops.layer_flops(cfg, True) == \
        2 * attention + 2 * hc_ops + 2 * 3 * c * 9216
    assert flops.token_flops(cfg) == 2 * flops.layer_flops(cfg, True) \
        + 8 * flops.layer_flops(cfg, False)


def test_flops_and_bytes_against_a_hand_computed_step():
    cfg = _published()
    c, vocab = 3584, 131072
    weights = 2 * (2 * 128_225_590 + 8 * 216_535_414 + c * vocab + c)
    assert flops.weight_bytes(cfg) == weights
    assert round(weights / 1e9, 2) == 4.92            # ISSUE: 4.92 GB
    # 48 sequences of 9300 cached tokens: 1152 bytes a token and layer
    live = 48 * 9300
    ops, byts = flops.decode_step(cfg, 48, live)
    cache = 10 * 1152 * live
    assert byts == weights + cache
    assert round(cache / 1e9, 2) == 5.14
    attention = 10 * 2 * 32 * (576 + 512) * live      # 1088 a head and row
    assert ops == 48 * (flops.token_flops(cfg) + 2 * c * vocab) + attention
    assert 300e9 < attention < 320e9                  # ISSUE: about 310 G
    peaks = run.load_json(bench_tiny.REPO, 'benchmark', 'peaks.json')[
        'TPU v5 lite']
    assert byts / peaks['hbm_bytes_per_s'] > ops / peaks['bf16_flops_per_s']
    assert 12.0 < 1e3 * byts / peaks['hbm_bytes_per_s'] < 12.6
    # what a layout pads a row to is not the yardstick's: 576 columns
    assert flops.row_columns(cfg) == 576


def test_mla_decode_attention_against_a_hand_computed_layer():
    cfg = _published()
    live = 48 * 9300
    ops, byts = flops.mla_decode_attention(cfg, 48, live)
    assert ops == 2 * 32 * 1088 * live == 31_083_724_800
    assert byts == 1152 * live + 4 * 48 * 32 * 1088
    peaks = run.load_json(bench_tiny.REPO, 'benchmark', 'peaks.json')[
        'TPU v5 lite']
    # bound by bytes: 0.63 ms a layer against 0.16 ms of the MXU
    assert 0.62 < 1e3 * byts / peaks['hbm_bytes_per_s'] < 0.64
    assert 0.15 < 1e3 * ops / peaks['bf16_flops_per_s'] < 0.17


def test_serve_flops_per_token_counts_no_prefill():
    cfg = _published()
    traffic = run.load_json(bench_tiny.REPO, 'benchmark', 'traffic',
                            'docqa-resident-saturated.json')
    per_out = flops.serve_flops_per_token(cfg, traffic)
    token = flops.token_flops(cfg) + 2 * 3584 * 131072
    per_position = 10 * 2 * 32 * 1088
    # a reply token: layers and head once, and between the shortest and
    # the longest context of cached positions
    assert token + per_position * 2048 < per_out < \
        token + per_position * (16384 + 1024)
    # a count that ran every document's prefill a request would be some
    # 8900 / 289 = 30 tokens' work a reply token
    assert per_out < 5 * token


FACTS = {
    'steps': 300, 'active_per_step': 48.0,
    'live_kv_tokens_per_step': 48 * 9300.0,
    'peaks': {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9},
    'xplane': {
        'modules': {'jit_fn_step(1)': [6.0, 298], 'jit_page_copy(2)': [0.1, 9]},
        'device_ops': [
            ['%mxnet_tpu_paged_decode_walk.3 f32[48,32,512] custom-call', 0.3],
            ['%fusion.12 bf16[48,9216] fusion', 0.25],
            ['%mxnet_tpu_paged_decode_walk.5 f32[48,32,512] custom-call', 0.3]]}}


def test_kernel_roofline_on_canned_facts():
    cfg = _published()
    facts = dict(FACTS, config=cfg)
    args = dict(kernel='mxnet_tpu_paged_decode_walk', module='^jit_fn',
                work='mla_decode_attention')
    # two layers' calls found, 298 calls each of 0.6295 ms at the least
    _ops, byts = flops.mla_decode_attention(cfg, 48, 48 * 9300)
    want = 100.0 * (byts / 819e9) * 298 * 2 / 0.6
    got = kernel_roofline.read(facts, **args)
    assert got == pytest.approx(want) and 60 < got < 65
    # the kernel's name absent (the parent, or a gathered step): nothing
    parent = dict(facts, xplane=dict(FACTS['xplane'], device_ops=[
        ['%fusion.12 bf16[48,9216] fusion', 0.25]]))
    assert kernel_roofline.read(parent, **args) is None
    assert kernel_roofline.read(dict(facts, xplane=None), **args) is None
    assert kernel_roofline.read(dict(facts, steps=0), **args) is None
