"""The ``granitemoehybrid`` decode family (serving/decode/granite.py) on the
CPU at a small size, against the plain reference (benchmark/reference/
granitemoehybrid.py): hidden 64, layers mamba x2 + attention + mamba, 8
Mamba heads of 16 with a state of 16 in chunks of 8, 4 query heads on 2 KV
heads of 16, 8 experts top-3 of which some are held, a shared MLP of 48,
page 4, vocabulary 96, float32 weights.

Tolerances. Program and reference are float32 on both sides here and differ
only in the order of their sums (the chunked recurrence, blocked attention,
grouped experts), so logits agree to ``TOL`` = 2e-6: logits are of order
0.01 (the embedding is small beside the stream and the head divides by 16),
and float32 sums of 64-640 terms are good to 1e-7 of that. A wrong mask,
state, gate or page moves a logit by far more (the six planted faults of
tests/benchmark_tests/test_bench_granite_cell.py read 2e-4 to 1.2e-2 on the
served tokens' gap).
"""
import inspect
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                'benchmark_tests'))

import bench_tiny_g4h  # noqa: E402

from benchmark.reference import granitemoehybrid as ref  # noqa: E402
from benchmark.systems import granitemoehybrid as systems  # noqa: E402
from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.serving import decode  # noqa: E402
from mxnet_tpu.serving.decode import (DecodeEngine,  # noqa: E402
                                      FamilyUnsupported, GraniteHybridLM,
                                      PagedDecodeProgram)
from mxnet_tpu.serving.decode import engine as engine_module  # noqa: E402
from mxnet_tpu.serving.decode.paged import slot_state_bytes  # noqa: E402

TOL = 2e-6
FAMILY = 'granitemoehybrid'


def _cfg(tie=0.0, **over):
    """The toy configuration; the reference's near-tie rule off unless a
    test asks for it, so that every position is compared."""
    cfg = bench_tiny_g4h.config()
    cfg['precision'] = dict(cfg['precision'], router_tie_margin=tie)
    cfg.update(over)
    return cfg


def _weights(cfg, seed=3):
    return {k: v.astype('float32')
            for k, v in ref.make_weights(cfg, seed).items()}


def _model(cfg):
    return GraniteHybridLM(systems.model_config(cfg))


def _ref_logits(cfg, w, tokens):
    x, _ = ref.hidden(cfg, w, np.asarray(tokens, 'int32'))
    return np.asarray(ref.head(x, w['lnf_g'], w['embed'],
                               cfg['rms_norm_eps'],
                               float(cfg['logits_scaling']), None))


def _program(cfg, w, **kw):
    kw = dict(dict(slots=4, prefill_buckets=[8, 16, 32], page_size=4,
                   emit_logits=True), **kw)
    return PagedDecodeProgram(_model(cfg), systems.program_params(w), **kw)


@pytest.fixture(scope='module')
def toy():
    cfg = _cfg()
    w = _weights(cfg)
    return cfg, w, _program(cfg, w)


def _tokens(n, seed=0, vocab=96):
    return [int(t) for t in np.random.RandomState(seed).randint(0, vocab, n)]


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------

def test_full_forward_equals_the_plain_reference():
    cfg = _cfg()
    w = _weights(cfg)
    tokens = _tokens(45, 1)
    got = np.asarray(_model(cfg).full_forward(
        systems.program_params(w), np.asarray([tokens], 'int32')))[0]
    want = _ref_logits(cfg, w, tokens)
    assert np.abs(want).max() > 5e-3         # not a comparison of zeros
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize('length', [5, 8, 13, 16, 21, 24])
def test_chunked_scan_equals_the_sequential_recurrence(length):
    """``_scan_chunks`` (chunks of 8) against one position after another,
    at lengths that are and are not multiples of the chunk: outputs and
    the state it leaves."""
    import jax.numpy as jnp
    cfg = _cfg()
    model = _model(cfg)
    rs = np.random.RandomState(length)
    heads, p, n = 8, 16, 16
    xh = rs.randn(length, heads, p).astype('float32')
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(0.5),
                           (length, heads))).astype('float32')
    b, c = (rs.randn(length, n).astype('float32') for _ in range(2))
    a_log = np.log(rs.uniform(1, 16, heads)).astype('float32')
    y, state = model._scan_chunks(*(jnp.asarray(v)
                                    for v in (xh, dt, b, c, a_log)))
    s = np.zeros((heads, p, n))
    want = np.zeros((length, heads, p))
    for t in range(length):
        decay = np.exp(dt[t] * -np.exp(a_log))
        s = decay[:, None, None] * s \
            + (dt[t][:, None] * xh[t])[:, :, None] * b[t][None, None, :]
        want[t] = s @ c[t]
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    assert np.abs(np.asarray(state) - s).max() < 1e-5


@pytest.mark.parametrize('n', [3, 5, 8, 13, 16, 21, 30])
def test_prefill_padded_to_its_bucket_then_decode_equals_the_reference(
        toy, n):
    """A prompt of ``n`` tokens (buckets 8, 16, 32; the recurrence's chunk
    is 8) prefilled into slot 2 and then decoded through the cache, every
    step against the reference's one full pass: the padding leaves the
    state of position ``n - 1``, the step carries it on."""
    import threading
    from mxnet_tpu.serving.decode.paged import PageOwner
    cfg, w, prog = toy
    tokens = np.asarray(_tokens(40, 100 + n), 'int32')
    want = _ref_logits(cfg, w, tokens)
    counts = {}
    owner = PageOwner(prog.page_spec, prog.pool_pages, threading.Lock(),
                      True, counts)
    pool = prog.new_cache()
    # another sequence's state in the slot, as a slot that was used has
    garbage = np.random.RandomState(n)
    pool = {k: v + garbage.standard_normal(v.shape).astype(v.dtype)
            if k.endswith(('_ssm', '_conv')) else v
            for k, v in pool.items()}
    rec = owner.open(2)
    ids = owner.place(rec, n)
    assert ids['slot'] == 2
    pool, _tok, logits = prog.run_prefill(pool, tokens[:n], ids)
    worst = np.abs(logits - want[n - 1]).max()
    for pos in range(n, 40):
        assert owner.make_writable(rec, pos, pos, None)
        feed, at = np.zeros(4, 'int32'), np.zeros(4, 'int32')
        feed[2], at[2] = tokens[pos], pos
        pool, _toks, logits = prog.run_step(
            pool, feed, at, owner.tables(4, [(2, rec)]))
        worst = max(worst, np.abs(logits[2] - want[pos]).max())
    assert worst < TOL
    assert owner.live_gauges() == {
        'state_bytes_live': slot_state_bytes(prog.page_spec)}


def test_an_idle_slot_keeps_its_state_through_a_step(toy):
    _cfg_, _w, prog = toy
    pool = prog.new_cache()
    rs = np.random.RandomState(0)
    pool = {k: v + rs.standard_normal(v.shape).astype(v.dtype)
            if k.endswith(('_ssm', '_conv')) else v
            for k, v in pool.items()}
    before = {k: np.asarray(v) for k, v in pool.items()
              if k.endswith(('_ssm', '_conv'))}
    feed, at = np.zeros(4, 'int32'), np.zeros(4, 'int32')
    feed[1], at[1] = 7, 3                        # slot 1 steps, the others idle
    pool, _toks, _logits = prog.run_step(
        pool, feed, at, np.zeros((4, prog.max_pages), 'int32'))
    for name, was in before.items():
        now = np.asarray(pool[name])
        assert np.array_equal(now[[0, 2, 3]], was[[0, 2, 3]]), name
        assert not np.array_equal(now[1], was[1]), name


def test_two_shares_add_up_to_the_uncut_layer():
    """The guide's shares test: one Mamba layer and 8 experts cut into the
    two shares of the deployment (experts 0-3, 4-7; mixer, router and
    shared MLP whole on both). The two shares' routed sums, with the
    stream after the mixer and the shared MLP counted once, equal the
    uncut reference layer."""
    import jax.numpy as jnp
    base = _cfg(num_hidden_layers=1, layer_types=['mamba', 'attention'],
                held_experts=list(range(8)), num_local_experts=8)
    w = _weights(base, seed=9)
    tokens = np.asarray(_tokens(20, 7), 'int32')
    x = base['embedding_multiplier'] * np.asarray(w['embed'])[tokens]
    lw = {k.split('.', 1)[1]: v for k, v in w.items() if k.startswith('l0.')}
    want = np.asarray(ref.layer(
        jnp.asarray(x), lw, ref._cfg_key(base), tuple(range(8)), 'mamba',
        0.0, None)[0])
    m = base['residual_multiplier']
    total, once = np.zeros_like(x), None
    for share in (range(0, 4), range(4, 8)):
        cfg = dict(base, held_experts=list(share), num_local_experts=4)
        sw = dict(w)
        for name in ('w1', 'w3', 'w2'):
            sw['l0.' + name] = w['l0.' + name][share.start:share.stop]
        model = _model(cfg)
        p = systems.program_params(sw)
        lp = lambda name, p=p: p['l0_' + name]               # noqa: E731
        mixed, _state, _last = model._mamba_sequence(
            lp, model._rms(jnp.asarray(x), lp('ln1_g')), 20)
        u = jnp.asarray(x) + m * mixed
        n = model._rms(u, lp('ln2_g'))
        w_gate, top_i = model._route(lp, n)
        routed, counts = model._experts.grouped(
            n, w_gate, top_i, 20, lp('w1'), lp('w3'), lp('w2'))
        assert int(counts.sum()) < 20 * 3        # some went to the other chip
        once = np.asarray(u + m * model._shared(lp, n))
        total += m * np.asarray(routed)
    assert np.abs(total + once - want).max() < 2e-6


def test_router_selects_in_float32_and_gates_over_the_selected():
    """The gate is a softmax over the top_k selected logits, held here or
    not, from a float32 product whatever the model's dtype."""
    import jax
    import jax.numpy as jnp
    cfg = _cfg()
    model = GraniteHybridLM(dict(systems.model_config(cfg),
                                 dtype='bfloat16'))
    rs = np.random.RandomState(4)
    n = rs.randn(12, 64).astype('float32')
    wr = (rs.randn(64, 8) / 8).astype('float32')
    with jax.default_matmul_precision('bfloat16'):
        gate, ids = model._route(lambda name: jnp.asarray(wr), jnp.asarray(n))
    logits = n.astype('float64') @ wr.astype('float64')
    want_ids = np.argsort(-logits, -1)[:, :3]
    assert np.array_equal(np.asarray(ids), want_ids)
    top = np.take_along_axis(logits, want_ids, -1)
    want = np.exp(top - top.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    assert np.abs(np.asarray(gate) - want).max() < 1e-6


def test_reference_leaves_out_near_tied_positions_and_no_others():
    cfg = _cfg(tie=0.05)
    w = _weights(cfg)
    tokens = np.asarray(_tokens(40, 5), 'int32')
    _x, near = ref.hidden(cfg, w, tokens)
    near = np.asarray(near)
    assert 0 < near.sum() < 40
    prompt, out = list(tokens[:10]), list(tokens[10:])
    rows = ref.next_token_logits(cfg, w, [prompt], [out])[0]
    zero = ~rows.any(-1)
    assert np.array_equal(zero, near[9:39])
    # a control pass (a dtype given) compares every position
    low = ref.next_token_logits(cfg, w, [prompt], [out],
                                dtype='bfloat16')[0]
    assert low.any(-1).all()


# ---------------------------------------------------------------------------
# recurrent state beside pages in one cache manager
# ---------------------------------------------------------------------------

def test_cache_entries_and_their_bytes(toy):
    _cfg_, _w, prog = toy
    spec = prog.page_spec
    assert sorted(spec.entries) == ['l2_k', 'l2_v']
    assert sorted(spec.slot_entries) == ['l0_conv', 'l0_ssm', 'l1_conv',
                                         'l1_ssm', 'l3_conv', 'l3_ssm']
    # a sequence's state: 3 layers x (8 x 16 x 16 float32 + 3 x 160 float32)
    state = 3 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 4)
    assert slot_state_bytes(spec) == state == 30336
    page = 2 * 4 * 32 * 4                            # K and V, 4 rows of 32
    assert prog.page_bytes() == page
    assert prog.pages == 4 * 16 + 1
    assert prog.cache_bytes() == 65 * page + 4 * state
    assert prog.per_sequence_bytes(10) == 3 * page + state
    assert prog._manifest_extra()['state_bytes_per_slot'] == state
    pool = prog.new_cache()
    assert pool['l0_ssm'].shape == (4, 8, 16, 16)
    assert str(pool['l0_ssm'].dtype) == 'float32'
    assert pool['l1_conv'].shape == (4, 3, 160)
    assert pool['l2_k'].shape == (65, 4, 32)
    again = type(spec).from_json(spec.to_json())
    assert again.slot_entries == spec.slot_entries
    assert again.entries == spec.entries


def _served_equal_reference(cfg, w, prompts, outs):
    for prompt, out in zip(prompts, outs):
        lg = ref.next_token_logits(cfg, w, [prompt], [out])[0]
        assert [int(r.argmax()) for r in lg] == list(out)


def test_a_slot_another_sequence_left_serves_what_a_fresh_engine_serves(
        toy):
    cfg, w, prog = toy
    prompts = [_tokens(n, 40 + n) for n in (5, 19, 30, 12, 9, 27, 8, 16)]
    eng = DecodeEngine(prog, max_new_tokens=24, prefill_interleave=2)
    try:
        # eight requests through four slots: every slot is used twice
        streams = [eng.generate(p, max_new_tokens=20) for p in prompts]
        outs = [s.result(timeout=120) for s in streams]
        stats = eng.stats()
        counts = stats['counts']
        assert counts['prefills'] == 8 and counts['prefix_hits'] == 0
        assert counts['state_bytes_live'] == 0
        assert counts['ssm_prefill_chunks'] == sum(
            3 * -(-prog.policy.bucket_for(len(p)) // 8) for p in prompts)
        assert counts['moe_assignments'] == \
            (counts['tokens'] - counts['prefills']) * 3 * 4
        share = counts['moe_assignments_here'] / counts['moe_assignments']
        assert 0.3 < share < 0.7                 # 4 of 8 experts are held
        assert stats['pages']['pages_free'] == 64
        assert 'prefix_entries' not in stats['pages']
        accounting = eng.cache_accounting()
        assert accounting['cache_bytes'] == prog.cache_bytes()
        assert accounting['per_sequence_bytes_max'] \
            == prog.per_sequence_bytes()
    finally:
        eng.close()
    _served_equal_reference(cfg, w, prompts, outs)
    fresh = DecodeEngine(_program(cfg, w), max_new_tokens=24)
    try:
        for prompt, out in zip(prompts[4:], outs[4:]):
            assert fresh.generate(prompt, max_new_tokens=20).result(
                timeout=120) == out
    finally:
        fresh.close()
    assert all(v == 1 for v in prog.trace_counts.values())


def test_held_bytes_count_the_state_of_sequences_in_flight(toy):
    _cfg_, _w, prog = toy
    eng = DecodeEngine(prog, max_new_tokens=64)
    try:
        stream = eng.generate(_tokens(6, 2), max_new_tokens=40)
        next(iter(stream))                       # admitted, still decoding
        accounting = eng.cache_accounting()
        live = eng.stats()['counts']['state_bytes_live']
        stream.result(timeout=120)
    finally:
        eng.close()
    state = slot_state_bytes(prog.page_spec)
    assert live == state
    assert accounting['per_sequence_bytes_amortized'] >= \
        state + prog.page_bytes()


def test_the_same_prompt_twice_shares_nothing_and_serves_the_same(toy):
    """K/V pages found by their tokens without the state at that boundary
    would decode wrongly: a cache with slot entries registers and shares
    no prefix, whatever the engine is asked for."""
    cfg, w, prog = toy
    prompt = _tokens(21, 77)
    eng = DecodeEngine(prog, max_new_tokens=16, prefix_cache=True)
    try:
        first = eng.generate(prompt, max_new_tokens=12).result(timeout=120)
        second = eng.generate(prompt, max_new_tokens=12).result(timeout=120)
        counts = eng.stats()['counts']
    finally:
        eng.close()
    assert first == second
    assert counts['prefix_hits'] == 0 and counts['prefix_tokens_saved'] == 0
    assert counts['prefills'] == 2
    _served_equal_reference(cfg, w, [prompt], [first])


def test_unimplemented_paths_raise_a_typed_error_naming_the_family(toy):
    cfg, w, prog = toy
    model, params = _model(cfg), systems.program_params(w)
    cases = [
        lambda: model.cache_spec(),
        lambda: serving.freeze_decode(model, params, paged=False, slots=2),
        lambda: serving.freeze_decode(model, params, slots=2, spec_k=2,
                                      page_size=4, max_len=64),
        lambda: serving.freeze_decode(model, params, slots=2,
                                      adapter_rank=4, page_size=4),
        lambda: prog.fallback_generate([1, 2, 3], 4),
        lambda: prog.export_pages(None, [1]),
        lambda: prog.import_pages(None, {}, [1]),
        lambda: model.paged_verify(params, None, None, None, None)]
    for case in cases:
        with pytest.raises(FamilyUnsupported) as err:
            case()
        assert err.value.family == FAMILY
        assert FAMILY in str(err.value)
    eng = DecodeEngine(prog)
    try:
        with pytest.raises(FamilyUnsupported):
            eng.generate([1, 2, 3], prefill_only=True)
        with pytest.raises(FamilyUnsupported):
            eng.import_sequence({'prompt': [1], 'emitted': [], 'pos': 1})
    finally:
        eng.close()
    assert decode.model_from_config(
        FAMILY, systems.model_config(cfg)).family == FAMILY


def test_the_scheduler_names_no_kind_of_layer_and_no_family():
    """PR 32's invariant: ``engine.py`` asks the page owner and the
    program, and branches on no kind of layer or family."""
    source = inspect.getsource(engine_module).lower()
    for word in ('mamba', 'ssm', 'granite', 'cohere', 'slot_entries',
                 'window_entries', "'window'", "'full'", 'state_bytes'):
        assert word not in source, word


def test_named_scopes_of_the_step_program(toy):
    _, _, prog = toy
    text = prog.compile_step().as_text()
    for i, mixer in enumerate(('mamba/conv', 'mamba/ssm_update',
                               'kv_gather', 'mamba/conv')):
        assert 'layer%d/%s' % (i, mixer) in text, (i, mixer)
        for scope in ('moe/router', 'moe/experts', 'moe/shared'):
            assert 'layer%d/%s' % (i, scope) in text, (i, scope)
    assert 'lm_head' in text and 'embed' in text
    names = {k: prog._compiled[k].as_text().split('HloModule ')[1]
             .split(',')[0].split(' ')[0] for k in prog._compiled}
    assert names['step'] == 'jit_fn_step'


# ---------------------------------------------------------------------------
# the attention layer's step: the walk on a TPU, the gather anywhere else
# ---------------------------------------------------------------------------

def test_kv_pages_walked_and_view_count_the_attention_layers_alone(toy):
    """One attention layer in the toy's four: a step books ``position //
    page_size + 1`` pages a live sequence and ``slots x max_pages`` of
    view; the Mamba layers' state is no page."""
    _cfg_, _w, prog = toy
    spec = prog.page_spec
    prompts = [_tokens(6, 2), _tokens(13, 5)]
    new = [9, 5]
    eng = DecodeEngine(prog, max_new_tokens=16)
    try:
        outs = [eng.generate(p, max_new_tokens=n).result(timeout=120)
                for p, n in zip(prompts, new)]
        counts = eng.stats()['counts']
    finally:
        eng.close()
    walked = sum(pos // spec.page_size + 1
                 for prompt, out in zip(prompts, outs)
                 for pos in range(len(prompt), len(prompt) + len(out) - 1))
    assert counts['kv_pages_walked'] == walked
    assert counts['kv_pages_view'] == \
        counts['steps'] * prog.slots * spec.max_pages


def test_the_attention_step_traced_for_the_cpu_gathers(toy):
    """``_attention_step`` chooses by where it is placed
    (``paged.walks_pages``), at the published head geometry too: on the
    CPU rig it holds the gather and no kernel."""
    import jax
    from mxnet_tpu.serving.decode.paged import pool_avals, walks_pages
    model = GraniteHybridLM(dict(
        _model(_cfg()).config, hidden=64, head_dim=128, heads=32,
        kv_heads=8, layer_types=['attention'], dtype='bfloat16'))
    spec = model.paged_spec(16)
    pool = pool_avals(spec, 9, 0, 2)
    assert tuple(pool['l0_k'].shape) == (9, 16, 1024)
    assert not walks_pages(pool['l0_k'].shape, pool['l0_k'].dtype)
    params = jax.eval_shape(lambda: model.init_params(0))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, 'int32')

    text = str(jax.make_jaxpr(model.paged_step)(
        params, pool, i32(2), i32(2), i32(2, spec.max_pages)))
    assert 'pallas_call' not in text
    assert text.count('gather[') >= 2
