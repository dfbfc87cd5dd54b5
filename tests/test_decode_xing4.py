"""The ``xing4_0`` decode family (serving/decode/xing4.py) on the CPU at a
small size, against the plain reference (benchmark/reference/xing4_0.py):
hidden 48 in four residual streams, one dense layer and two expert layers (8
experts, top-2 with a selection bias, a shared expert), 4 heads of 16 + 8 on
a latent of 32 with a roped key of 8 (a cache row of 40 columns padded to
128), YaRN from 32 positions by a factor of 4, page 4, vocabulary 96,
float32 weights.

Tolerances. Program and reference are float32 on both sides here and differ
in the order of their sums and in the form of the attention (blocked and
up-projected in the prefill, absorbed in the step, plain in the reference),
so logits of order 3 agree to ``TOL`` = 2e-5. A wrong mask, page, gate or
coefficient moves a logit by far more (the planted faults of
tests/benchmark_tests/test_bench_xing4_cell.py read 1e-3 and up on the
served tokens' gap).
"""
import inspect
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                'benchmark_tests'))

import bench_tiny_x4  # noqa: E402

from benchmark.reference import xing4_0 as ref  # noqa: E402
from benchmark.systems import xing4_0 as systems  # noqa: E402
from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.serving import decode  # noqa: E402
from mxnet_tpu.serving.decode import (DecodeEngine,  # noqa: E402
                                      FamilyUnsupported,
                                      PagedDecodeProgram)
from mxnet_tpu.serving.decode import engine as engine_module  # noqa: E402
from mxnet_tpu.serving.decode.paged import (PageOwner,  # noqa: E402
                                            gather_pages)
from mxnet_tpu.serving.decode.xing4 import Xing4LM  # noqa: E402

TOL = 2e-5
FAMILY = 'xing4_0'


def _cfg(tie=0.0, **over):
    """The toy configuration; the reference's near-tie rule off unless a
    test asks for it, so that every position is compared."""
    cfg = bench_tiny_x4.config(**over)
    cfg['precision'] = dict(cfg['precision'], router_tie_margin=tie)
    return cfg


def _weights(cfg, seed=3):
    return {k: v.astype('float32')
            for k, v in ref.make_weights(cfg, seed).items()}


def _model(cfg):
    return Xing4LM(systems.model_config(cfg))


def _ref_logits(cfg, w, tokens):
    x, _ = ref.hidden(cfg, w, np.asarray(tokens, 'int32'))
    return np.asarray(ref.head(x, w['lnf_g'], w['head'],
                               cfg['rms_norm_eps'], None))


def _program(cfg, w, **kw):
    kw = dict(dict(slots=4, prefill_buckets=[8, 16, 32, 64], page_size=4,
                   emit_logits=True), **kw)
    return PagedDecodeProgram(_model(cfg), systems.program_params(w), **kw)


@pytest.fixture(scope='module')
def toy():
    cfg = _cfg()
    w = _weights(cfg)
    return cfg, w, _program(cfg, w)


def _tokens(n, seed=0, vocab=96):
    return [int(t) for t in np.random.RandomState(seed).randint(0, vocab, n)]


def _owner(prog, counts=None):
    return PageOwner(prog.page_spec, prog.pool_pages, threading.Lock(),
                     True, {} if counts is None else counts)


def _step(prog, pool, owner, slot, rec, token, pos):
    feed, at = np.zeros(4, 'int32'), np.zeros(4, 'int32')
    feed[slot], at[slot] = token, pos
    pool, _toks, logits = prog.run_step(pool, feed, at,
                                        owner.tables(4, [(slot, rec)]))
    return pool, logits[slot]


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------

def test_full_forward_equals_the_plain_reference():
    cfg = _cfg()
    w = _weights(cfg)
    tokens = _tokens(70, 1)                  # past YaRN's original 32
    got = np.asarray(_model(cfg).full_forward(
        systems.program_params(w), np.asarray([tokens], 'int32')))[0]
    want = _ref_logits(cfg, w, tokens)
    assert np.abs(want).max() > 1.0          # not a comparison of zeros
    assert np.abs(got - want).max() < TOL


def test_streams_and_mlp_in_blocks_of_rows_equal_the_whole(monkeypatch):
    """A long prefill reads and writes its streams, runs its dense MLP and
    routes through its experts a block of rows at a time (2048, 2048 and
    4096 as served): here in blocks of 8, 8 and 16."""
    from mxnet_tpu.serving.decode import xing4
    cfg = _cfg()
    w = _weights(cfg)
    tokens = np.asarray([_tokens(64, 4)], 'int32')
    model, params = _model(cfg), systems.program_params(w)
    whole = np.asarray(model.full_forward(params, tokens))
    monkeypatch.setattr(xing4, '_STREAM_ROWS', 8)
    monkeypatch.setattr(xing4, '_MLP_ROWS', 8)
    monkeypatch.setattr(xing4, '_MOE_ROWS', 16)
    blocked = np.asarray(model.full_forward(params, tokens))
    assert np.abs(blocked - whole).max() < 1e-5
    assert np.abs(blocked[0] - _ref_logits(cfg, w, tokens[0])).max() < TOL


@pytest.mark.parametrize('n', [3, 8, 13, 21, 30, 33, 47, 64])
def test_prefill_padded_to_its_bucket_then_decode_equals_the_reference(
        toy, n):
    """A prompt of ``n`` tokens (buckets 8, 16, 32, 64) prefilled and then
    decoded through the latent cache, every step against the reference's
    one full pass: the prefill's up-projected attention and the step's
    absorbed one read the same rows, on both sides of YaRN's original 32
    positions."""
    cfg, w, prog = toy
    tokens = np.asarray(_tokens(80, 100 + n), 'int32')
    want = _ref_logits(cfg, w, tokens)
    owner = _owner(prog)
    pool = prog.new_cache()
    rec = owner.open(2)
    ids = owner.place(rec, n)
    pool, _tok, logits = prog.run_prefill(pool, tokens[:n], ids)
    worst = np.abs(logits - want[n - 1]).max()
    for pos in range(n, 80):
        assert owner.make_writable(rec, pos, pos, None)
        pool, logits = _step(prog, pool, owner, 2, rec, tokens[pos], pos)
        worst = max(worst, np.abs(logits - want[pos]).max())
    assert worst < TOL


def test_the_absorbed_step_equals_up_projected_attention_on_the_same_cache(
        toy):
    """The step's context, ``Wkvb`` absorbed into query and output, against
    keys and values up-projected by head from the very rows the cache
    holds."""
    import jax.numpy as jnp
    cfg, w, prog = toy
    model, params = _model(cfg), systems.program_params(w)
    p = lambda name: params['l1_' + name]                     # noqa: E731
    rs = np.random.RandomState(5)
    positions = np.asarray([37, 0, 9, 52], 'int32')
    tables = np.zeros((4, prog.max_pages), 'int32')
    free = list(range(1, prog.pages))
    for s, pos in enumerate(positions):
        if pos:
            for j in range(pos // 4 + 1):
                tables[s, j] = free.pop()
    pool = {'l1_c': jnp.asarray(
        rs.randn(prog.pages, 4, model.row_width), 'float32')}
    pool['l1_c'] = pool['l1_c'].at[:, :, 40:].set(0.0)
    h = jnp.asarray(rs.randn(4, 48), 'float32')
    got = np.asarray(model._attention_step(
        p, h, pool, 'l1_c', jnp.asarray(positions), jnp.asarray(tables)))
    # the same cache (the step has appended its rows), read the plain way
    rows = np.asarray(gather_pages(pool['l1_c'], jnp.asarray(tables)))
    q_nope, q_pe = (np.asarray(a) for a in model._queries(
        p, h, jnp.asarray(positions)))
    wk, wv = (np.asarray(a, 'float64') for a in model._kv_b(p))
    want = np.zeros((4, 4 * 16))
    for s, pos in enumerate(positions):
        c = rows[s, :pos + 1, :32].astype('float64')
        k_pe = rows[s, :pos + 1, 32:40].astype('float64')
        k_nope = np.einsum('lc,chd->lhd', c, wk)
        v = np.einsum('lc,chd->lhd', c, wv)
        sc = model.score_scale * (
            np.einsum('hd,lhd->hl', q_nope[s], k_nope)
            + np.einsum('hd,ld->hl', q_pe[s], k_pe))
        att = np.exp(sc - sc.max(-1, keepdims=True))
        att /= att.sum(-1, keepdims=True)
        want[s] = np.einsum('hl,lhd->hd', att, v).reshape(-1)
    want = want @ np.asarray(p('o_w'), 'float64')
    live = positions > 0
    assert np.abs(got[live] - want[live]).max() < 2e-5


def test_the_mixing_matrix_is_doubly_stochastic(toy):
    """Twenty Sinkhorn iterations, a column pass then a row pass each. The
    last pass leaves every row at 1 to rounding; how near the columns come
    depends on the matrix: Sinkhorn contracts by tanh(D / 4) a pass, D the
    log of the largest cross ratio, so a diagonal of e^4 over entries of
    about 1 (the seeded leaves) converges slowly and is still some
    percent off in its worst column, where a diagonal of e^1 over entries
    spread by 0.25 is doubly stochastic to 1e-5. Program and reference
    agree either way."""
    import jax.numpy as jnp
    cfg, w, _prog = toy
    model, params = _model(cfg), systems.program_params(w)
    x = jnp.asarray(np.random.RandomState(2).randn(50, 4, 48), 'float32')
    xs = tuple(x[:, i] for i in range(4))
    for layer, j in ((0, 1), (1, 2), (2, 1)):
        lw = {k.split('.', 1)[1]: v for k, v in w.items()
              if k.startswith('l%d.' % layer)}
        p = lambda name: params['l%d_%s' % (layer, name)]      # noqa: E731
        pre, post, res = (np.asarray(a)
                          for a in model._coefficients(p, j, xs))
        assert np.abs(res.sum(2) - 1).max() < 1e-5            # rows
        assert 1e-4 < np.abs(res.sum(1) - 1).max() < 0.15     # columns
        assert (res > 0).all() and res.std() > 0.05
        assert ((pre > 0) & (pre < 1)).all() and pre.std() > 0.05
        assert ((post > 0) & (post < 2)).all() and post.std() > 0.05
        # against the reference's own coefficients
        for got, want in zip((pre, post, res),
                             ref.coefficients(x, lw, j, cfg, None)):
            assert np.abs(got - np.asarray(want)).max() < 1e-5
        # a diagonal of e^1 over a spread of 0.25: both within 1e-5
        a, b = (params['l%d_hc%d_%s' % (layer, j, v)] for v in 'ab')
        mild = dict(params)
        mild['l%d_hc%d_b' % (layer, j)] = b.at[8:].set(b[8:] / 4.0)
        mild['l%d_hc%d_a' % (layer, j)] = a.at[2].set(0.25)
        q = lambda name: mild['l%d_%s' % (layer, name)]        # noqa: E731
        _pre, _post, res = (np.asarray(a)
                            for a in model._coefficients(q, j, xs))
        assert np.abs(res.sum(2) - 1).max() < 1e-5
        assert np.abs(res.sum(1) - 1).max() < 1e-5
        assert res.std() > 0.05


def test_redundant_streams_equal_the_plain_pre_norm_residual_model():
    """Static coefficients that make the streams redundant: ``a`` 0,
    ``b_pre`` logit(1/4) (the read is the streams' mean), ``b_post`` 0
    (every write is 1). The four streams then stay equal and the model is
    the plain pre-norm residual model of the same weights."""
    import jax.numpy as jnp
    cfg = _cfg()
    w = _weights(cfg)
    n = cfg['hc_mult']
    for name in list(w):
        if name.endswith(('hc1_a', 'hc2_a')):
            w[name] = jnp.zeros_like(w[name])
        elif name.endswith(('hc1_b', 'hc2_b')):
            w[name] = w[name].at[:n].set(np.log(0.25 / 0.75)) \
                .at[n:2 * n].set(0.0)
    model, params = _model(cfg), systems.program_params(w)
    tokens = jnp.asarray(_tokens(40, 11), 'int32')
    got = np.asarray(model.full_forward(params, tokens[None]))[0]
    # one stream: x' = x + F(RMSNorm_in(x))
    x = jnp.take(params['embed'], tokens, axis=0)
    for i in range(model.layers):
        p = lambda name: params['l%d_%s' % (i, name)]          # noqa: E731
        out, _rows = model._attention_sequence(
            p, model._rms(x, p('ln1_g')), jnp.arange(40))
        x = x + out
        out, _counts = model._mlp(p, i < model.dense_layers,
                                  model._experts.grouped,
                                  40)(model._rms(x, p('ln2_g')))
        x = x + out
    want = np.asarray(model._head(params, x))
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


def test_four_shares_add_up_to_the_uncut_layer():
    """The guide's shares test: 8 experts cut into the four shares of the
    deployment (experts 0-1, 2-3, 4-5, 6-7; router and shared expert whole
    on all four). The shares' routed sums, with the shared expert counted
    once, equal the uncut reference layer's expert sublayer."""
    import jax.numpy as jnp
    base = _cfg(held_experts=list(range(8)), n_routed_experts=8)
    w = _weights(base, seed=9)
    lw = {k.split('.', 1)[1]: v for k, v in w.items() if k.startswith('l1.')}
    h = jnp.asarray(np.random.RandomState(7).randn(20, 48), 'float32')
    want, _tight = ref.experts(h, lw, base, tuple(range(8)), None)
    total, shared = np.zeros((20, 48)), None
    for share in (range(0, 2), range(2, 4), range(4, 6), range(6, 8)):
        cfg = dict(base, held_experts=list(share), n_routed_experts=2)
        sw = dict(w)
        for name in ('w1', 'w3', 'w2'):
            sw['l1.' + name] = w['l1.' + name][share.start:share.stop]
        model, params = _model(cfg), systems.program_params(sw)
        p = lambda name, params=params: params['l1_' + name]   # noqa: E731
        gate, top_i = model._route(p, h)
        routed, counts = model._experts.grouped(
            h, gate, top_i, 20, p('w1'), p('w3'), p('w2'))
        assert 0 < int(counts.sum()) < 20 * 2    # the rest went elsewhere
        total += np.asarray(routed)
        both, _counts = model._mlp(p, False, model._experts.grouped, 20)(h)
        shared = np.asarray(both) - np.asarray(routed)
    assert np.abs(total + shared - np.asarray(want)).max() < 2e-6


def test_router_selects_by_score_plus_bias_and_gates_by_score():
    """Sigmoid scores from a float32 product whatever the model's dtype;
    the bias moves the selection and never the gate; gates are the
    selected scores renormalised and scaled by routed_scaling_factor."""
    import jax
    import jax.numpy as jnp
    cfg = _cfg()
    model = Xing4LM(dict(systems.model_config(cfg), dtype='bfloat16'))
    rs = np.random.RandomState(4)
    h = rs.randn(64, 48).astype('float32')
    wr = (rs.randn(48, 8) / 7).astype('float32')
    bias = (0.3 * rs.randn(8)).astype('float32')
    leaves = {'router_w': jnp.asarray(wr), 'router_b': jnp.asarray(bias)}
    with jax.default_matmul_precision('bfloat16'):
        gate, ids = model._route(leaves.__getitem__, jnp.asarray(h))
    score = 1 / (1 + np.exp(-(h.astype('float64') @ wr.astype('float64'))))
    want_ids = np.argsort(-(score + bias), -1)[:, :2]
    assert np.array_equal(np.asarray(ids), want_ids)
    # the bias changed some selections
    assert (np.sort(want_ids, -1)
            != np.sort(np.argsort(-score, -1)[:, :2], -1)).any()
    top = np.take_along_axis(score, want_ids, -1)
    want = 2.0 * top / top.sum(-1, keepdims=True)
    assert np.abs(np.asarray(gate) - want).max() < 1e-6
    assert np.abs(np.asarray(gate).sum(-1) - 2.0).max() < 1e-6


def test_the_next_token_module_equals_the_reference():
    cfg = _cfg(num_nextn_predict_layers=1)
    w = _weights(cfg, seed=6)
    assert 'mtp.proj' in w and 'mtp.router_w' in w
    model, params = _model(cfg), systems.program_params(w)
    tokens = np.asarray(_tokens(45, 3), 'int32')
    got = np.asarray(model.mtp_logits(params, tokens))
    want = np.asarray(ref.mtp_logits(cfg, w, tokens))
    assert got.shape == want.shape == (44, 96)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL
    # the served configuration has no such module and says so
    served = _model(_cfg())
    assert not any(k.startswith('mtp_') for k in served.param_shapes())
    with pytest.raises(ValueError):
        served.mtp_logits(params, tokens)


def test_reference_leaves_out_near_tied_positions_and_no_others():
    cfg = _cfg(tie=0.02)
    w = _weights(cfg)
    tokens = np.asarray(_tokens(60, 5), 'int32')
    _x, tightest = ref.hidden(cfg, w, tokens)
    near = np.asarray(tightest) < 0.02
    assert 0 < near.sum() < 60
    prompt, out = list(tokens[:10]), list(tokens[10:])
    rows = ref.next_token_logits(cfg, w, [prompt], [out])[0]
    zero = ~rows.any(-1)
    assert np.array_equal(zero, near[9:59])
    low = ref.next_token_logits(cfg, w, [prompt], [out],
                                dtype='bfloat16')[0]
    assert low.any(-1).all()


# ---------------------------------------------------------------------------
# latent rows in the paged cache manager
# ---------------------------------------------------------------------------

def test_cache_entries_and_their_bytes(toy):
    _cfg_, _w, prog = toy
    spec = prog.page_spec
    assert sorted(spec.entries) == ['l0_c', 'l1_c', 'l2_c']
    assert spec.entries_per_layer == 1 and not spec.slot_entries
    # a row: latent 32 + roped key 8, padded to one lane group of 128
    assert spec.entries['l0_c'] == ((128,), 'float32')
    page = 3 * 4 * 128 * 4                  # three layers, 4 rows of 128
    assert prog.page_bytes() == page
    assert prog.max_pages == 24 and prog.pages == 4 * 24 + 1
    assert prog.cache_bytes() == 97 * page
    assert prog.per_sequence_bytes(10) == 3 * page
    assert prog.per_sequence_bytes() == 24 * page
    assert prog.new_cache()['l2_c'].shape == (97, 4, 128)
    again = type(spec).from_json(spec.to_json())
    assert again.entries == spec.entries and again.entries_per_layer == 1
    # one entry a layer: a step at position 9 walks 3 pages in each of
    # the three layers, and a gathered view holds 24 a slot and layer
    owner = _owner(prog)
    assert owner.step_pages(4, [9]) == (3 * 3, 4 * 3 * 24)


def _served_equal_reference(cfg, w, prompts, outs):
    for prompt, out in zip(prompts, outs):
        lg = ref.next_token_logits(cfg, w, [prompt], [out])[0]
        assert [int(r.argmax()) for r in lg] == list(out)


def test_the_same_prompt_twice_is_one_hit_on_shared_latent_pages(toy):
    cfg, w, prog = toy
    prompt = _tokens(21, 77)
    eng = DecodeEngine(prog, max_new_tokens=16, prefix_cache=True)
    try:
        first = eng.generate(prompt, max_new_tokens=12).result(timeout=120)
        second = eng.generate(prompt, max_new_tokens=12).result(timeout=120)
        other = eng.generate(prompt[:16] + _tokens(9, 5),
                             max_new_tokens=12).result(timeout=120)
        stats = eng.stats()
        counts = stats['counts']
        accounting = eng.cache_accounting()
    finally:
        eng.close()
    assert first == second
    # the second asker found all 21 tokens and stepped on the last; the
    # third found four whole pages and streamed 9 tokens through the step
    assert counts['prefix_hits'] == 2
    assert counts['prefix_tokens_saved'] == 20 + 16
    assert counts['prefills'] == 1
    # slot-steps: 11 after the prefill's token, 1 + 11 behind the whole
    # hit, 9 + 11 behind the hit of four pages; top-2 in two expert layers
    assert counts['tokens'] == 36
    assert counts['moe_assignments'] == (11 + 12 + 20) * 2 * 2
    assert 0.2 < counts['moe_assignments_here'] \
        / counts['moe_assignments'] < 0.8        # 4 of 8 experts are held
    assert counts['kv_pages_walked'] > 0
    assert counts['kv_pages_view'] == counts['steps'] * 4 * 3 * 24
    assert stats['pages']['prefix_entries'] > 0
    assert accounting['cache_bytes'] == prog.cache_bytes()
    assert accounting['per_sequence_bytes_max'] == prog.per_sequence_bytes()
    _served_equal_reference(cfg, w, [prompt, prompt[:16] + _tokens(9, 5)],
                            [first, other])


def test_a_sharer_appending_past_a_shared_page_leaves_the_first_askers_rows(
        toy):
    """Two askers of one prompt, both live: the second shares the first's
    latent pages, and its first write (into the shared tail page) lands in
    a copy of its own."""
    cfg, w, prog = toy
    prompt = np.asarray(_tokens(22, 31), 'int32')        # 5 pages and a half
    counts = {}
    owner = _owner(prog, counts)
    pool = prog.new_cache()
    first = owner.open(0)
    ids = owner.place(first, 22)
    pool, _tok, _logits = prog.run_prefill(pool, prompt, ids)
    owner.register(list(prompt), ids)
    second = owner.open(1)
    covered, shared = owner.share_prefix(second, list(prompt))
    assert (covered, shared) == (21, 6) and counts['prefix_hits'] == 1
    assert list(second.tables['full'][:6]) == list(first.tables['full'][:6])
    before = {k: np.asarray(v)[np.asarray(ids)] for k, v in pool.items()}

    def copy(src, dst):
        nonlocal pool
        pool = prog.run_copy_page(pool, src, dst)

    # the sharer streams the last prompt token and then tokens of its own
    own = _tokens(10, 8)
    feed = [int(prompt[21])] + own
    want = _ref_logits(cfg, w, list(prompt) + own)
    worst = 0.0
    for j, token in enumerate(feed):
        pos = 21 + j
        assert owner.make_writable(second, pos, pos, copy)
        pool, logits = _step(prog, pool, owner, 1, second, token, pos)
        worst = max(worst, np.abs(logits - want[pos]).max())
    assert worst < TOL
    assert counts['cow_copies'] == 1
    assert second.tables['full'][5] != first.tables['full'][5]
    assert list(second.tables['full'][:5]) == list(first.tables['full'][:5])
    for name, was in before.items():
        assert np.array_equal(np.asarray(pool[name])[np.asarray(ids)], was)
    # and the first asker decodes on as if alone
    pool, logits = _step(prog, pool, owner, 0, first, own[0], 22)
    assert np.abs(logits - want[22]).max() < TOL


def test_unimplemented_paths_raise_a_typed_error_naming_the_family(toy):
    cfg, w, prog = toy
    model, params = _model(cfg), systems.program_params(w)
    cases = [
        lambda: model.cache_spec(),
        lambda: serving.freeze_decode(model, params, paged=False, slots=2),
        lambda: serving.freeze_decode(model, params, slots=2,
                                      adapter_rank=4, page_size=4),
        lambda: prog.fallback_generate([1, 2, 3], 4),
        lambda: model.paged_verify(params, None, None, None, None),
        lambda: model.lora_targets()]
    for case in cases:
        with pytest.raises(FamilyUnsupported) as err:
            case()
        assert err.value.family == FAMILY
        assert FAMILY in str(err.value)
    # found by name without the package importing it
    assert decode.model_from_config(
        FAMILY, systems.model_config(cfg)).family == FAMILY
    assert 'xing4' not in inspect.getsource(decode)


def test_a_latent_sequence_migrates_in_the_entry_keyed_payload():
    """One page list a sequence, so the ``seqstate`` payload carries the
    latent rows under their entries' names (``l<i>_c``) as it carries K
    and V: exported after the first token, re-chunked from pages of 4
    to pages of 8, decoded on without a prefill, token for token."""
    cfg = _cfg()
    w = _weights(cfg)
    prompt, n = _tokens(21, 5), 12
    alone = DecodeEngine(_program(cfg, w), timeout_s=60.0)
    src = DecodeEngine(_program(cfg, w), timeout_s=60.0)
    dst = DecodeEngine(_program(cfg, w, page_size=8), timeout_s=60.0)
    try:
        want = alone.generate(prompt, max_new_tokens=n).result(60)
        stream = src.generate(prompt, max_new_tokens=n)
        next(iter(stream))
        payload = src.export_sequence(stream, timeout=30)
        assert sorted(payload['entries']) == ['l0_c', 'l1_c', 'l2_c']
        got = list(payload['emitted']) + list(
            dst.import_sequence(payload, timeout=30))
        assert got == want
        assert dst.stats()['counts']['prefills'] == 0
    finally:
        for eng in (alone, src, dst):
            eng.close()


def test_the_scheduler_names_no_kind_of_layer_and_no_family():
    """PR 32's invariant: ``engine.py`` asks the page owner and the
    program, and branches on no kind of layer or family."""
    source = inspect.getsource(engine_module).lower()
    for word in ('latent', 'mla', 'xing4', 'hyper', 'entries_per_layer',
                 "'window'", "'full'"):
        assert word not in source, word


def test_named_scopes_of_the_step_and_the_prefill(toy):
    _, _, prog = toy
    step = prog.compile_step().as_text()
    prefill = prog.compile_prefill(16).as_text()
    for i in range(3):
        for scope in ('hyper_connection', 'mla_absorb', 'mla_walk'):
            assert 'layer%d/%s' % (i, scope) in step, (i, scope)
        assert 'layer%d/hyper_connection' % i in prefill
        assert 'layer%d/attn' % i in prefill
    for text in (step, prefill):
        assert 'layer0/mlp' in text
        for i in (1, 2):
            for scope in ('moe/router', 'moe/experts', 'moe/shared'):
                assert 'layer%d/%s' % (i, scope) in text, (i, scope)
        assert 'lm_head' in text and 'embed' in text
    assert 'kv_gather' in step                   # the CPU rig gathers
