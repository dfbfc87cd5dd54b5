"""The ``cohere2_moe`` decode family (serving/decode/cohere2.py) on the CPU
at a small size, against the plain reference (benchmark/reference/
cohere2_moe.py): hidden 64, 8 query heads on 2 KV heads of 16, 8 experts
top-2 of which some are held, two shared experts, window 8, page 4, layers
sliding x3 + full, vocabulary 96, float32 weights.

Tolerances. Program and reference are float32 on both sides here and differ
only in the order of their sums (blocked attention, grouped experts, a ring
of pages), so logits agree to ``TOL`` = 2e-5 (logits are of order 0.1-1;
float32 sums of 64-640 terms). A wrong mask, position, expert weight or
page moves a logit by far more at these sizes (the six planted faults of
tests/benchmark_tests/test_bench_cohere2_cell.py read 0.12 to 0.66 on the
served tokens' gap, bfloat16 operands 1.5e-3).
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                'benchmark_tests'))

import bench_tiny_cmda  # noqa: E402

from benchmark.flops import cohere2_moe as flops  # noqa: E402
from benchmark.reference import cohere2_moe as ref  # noqa: E402
from benchmark.systems import cohere2_moe as systems  # noqa: E402
from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.observability import spans  # noqa: E402
from mxnet_tpu.serving import decode  # noqa: E402
from mxnet_tpu.serving.decode import (Cohere2MoELM, DecodeEngine,  # noqa: E402
                                      FamilyUnsupported,
                                      PagedDecodeProgram)

TOL = 2e-5


def _cfg(tie=0.0, **over):
    """The toy configuration; the reference's near-tie rule off unless a
    test asks for it, so that every position is compared."""
    cfg = bench_tiny_cmda.config()
    cfg['precision'] = dict(cfg['precision'], router_tie_margin=tie)
    cfg.update(over)
    return cfg


def _weights(cfg, seed=3):
    return {k: v.astype('float32')
            for k, v in ref.make_weights(cfg, seed).items()}


def _model(cfg):
    return Cohere2MoELM(systems.model_config(cfg))


def _ref_logits(cfg, w, tokens):
    x, _ = ref.hidden(cfg, w, np.asarray(tokens, 'int32'))
    return np.asarray(ref.head(x, w['lnf_g'], w['embed'],
                               cfg['layer_norm_eps'],
                               float(cfg['logit_scale']), None))


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
# the block's mathematics
# ---------------------------------------------------------------------------

def test_full_forward_equals_the_plain_reference():
    cfg = _cfg()
    w = _weights(cfg)
    toks = np.asarray([_tokens(40, 1), _tokens(40, 2)], 'int32')
    got = np.asarray(_model(cfg).full_forward(systems.program_params(w),
                                              toks))
    for row, tokens in zip(got, toks):
        assert np.abs(row - _ref_logits(cfg, w, tokens)).max() < TOL


@pytest.mark.parametrize('held', [[0, 1, 2, 5], [6], [0, 1, 2, 3, 4, 5, 6, 7]])
def test_absent_experts_are_selected_and_weights_stay_normalised(held):
    """With 1, 4 or all 8 of 8 experts held the router still scores all 8
    and normalises over both selected: program and reference agree, and a
    share that holds fewer experts adds less."""
    cfg = _cfg(held_experts=held, num_experts=len(held))
    w = _weights(cfg)
    toks = np.asarray([_tokens(24, 4)], 'int32')
    got = np.asarray(_model(cfg).full_forward(systems.program_params(w),
                                              toks))[0]
    assert np.abs(got - _ref_logits(cfg, w, toks[0])).max() < TOL


@pytest.mark.parametrize('rows', [12, 40])
def test_every_token_on_one_held_expert_is_not_dropped(toy, rows):
    """Every row's first choice forced onto held expert 2: the grouped
    prefill and the dense step both compute all of its assignments, equal
    each other and the sum written out, and the grouped program is traced
    once for the balanced and the skewed routing alike (static shapes).
    12 rows fit one pass of the grouped product; 40 are more than an
    expert takes in a pass, so the skewed routing needs a second."""
    import jax
    import jax.numpy as jnp
    cfg, w, _ = toy
    model = _model(cfg)
    assert (rows > model._expert_block(rows)) == (rows == 40)
    lp = {k.split('.', 1)[1]: jnp.asarray(v) for k, v in w.items()
          if k.startswith('l0.')}
    n = jnp.asarray(np.random.RandomState(5).randn(rows, 64), 'float32')
    traces = []

    def route(skew, p, x):
        weights, ids = Cohere2MoELM._route(model, p, x)
        return weights, jnp.where(skew, ids.at[:, 0].set(2), ids)

    @jax.jit
    def both(x, skew):
        traces.append(1)
        model._route = lambda p, r: route(skew, p, r)
        try:
            grouped = model._moe_grouped(lp.__getitem__, x, rows)
            dense = model._moe_dense(lp.__getitem__, x,
                                     jnp.ones(rows, bool))
        finally:
            del model._route
        return grouped, dense

    for skew in (False, True):
        (routed_g, counts_g), (routed_d, counts_d) = both(n, skew)
        assert np.abs(np.asarray(routed_g)
                      - np.asarray(routed_d)).max() < TOL
        assert list(counts_g) == list(counts_d)
        weights, ids = route(skew, lp.__getitem__, n)
        want = np.zeros((rows, 64), 'float32')
        for t in range(rows):
            for k in range(2):
                if int(ids[t, k]) in model.held:
                    j = model.held.index(int(ids[t, k]))
                    want[t] += float(weights[t, k]) * np.asarray(ref.gated(
                        n[t:t + 1], lp['w1'][j], lp['w3'][j], lp['w2'][j],
                        None))[0]
        assert np.abs(np.asarray(routed_g) - want).max() < TOL
    assert int(counts_g[2]) >= rows    # a row whose second choice was 2 too
    assert len(traces) == 1


def test_router_selects_in_float32_whatever_the_model_dtype():
    """The router's number of its own: on a bfloat16 model, over 4096 rows
    of a float32 stream, the program's selections differ from float64
    arithmetic on the same operands in no row; with bfloat16 operands in
    the product (the planted fault) they differ in some, so the count can
    tell."""
    import jax.numpy as jnp
    cfg = _cfg()
    cfg['precision'] = dict(cfg['precision'], weights='bfloat16')
    model = _model(cfg)
    rs = np.random.RandomState(11)
    wr = jnp.asarray(rs.randn(64, 8) / 8.0, 'bfloat16')
    n = jnp.asarray(rs.randn(4096, 64), 'float32')
    exact = np.asarray(n, 'float64') @ np.asarray(wr.astype('float32'),
                                                  'float64')
    want = np.sort(np.argsort(-exact, -1)[:, :2], -1)

    def differ(ids):
        return int((np.sort(np.asarray(ids), -1) != want).any(-1).sum())

    weights, ids = model._route(lambda name: wr, n)
    assert differ(ids) == 0
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    low = jnp.einsum('th,he->te', n.astype('bfloat16'), wr,
                     preferred_element_type='float32')
    assert differ(np.argsort(-np.asarray(low), -1)[:, :2]) > 0


def test_reference_leaves_out_near_tied_positions_and_no_others():
    """The reference's rule, against the same scores computed apart: a
    position's row is zeros iff in some layer its 2nd and 3rd scores lie
    within the margin and one of the two experts is held; a margin of 0
    leaves every row, and the rows it keeps are the rows it gave without
    the rule."""
    cfg = _cfg(tie=0.02)
    w = _weights(cfg)
    prompt, out = _tokens(6, 31), _tokens(40, 32)
    got = ref.next_token_logits(cfg, w, [prompt], [out])[0]
    plain = ref.next_token_logits(_cfg(), w, [prompt], [out])[0]
    tokens = np.asarray(prompt + out, 'int32')
    held, near = set(cfg['held_experts']), np.zeros(len(tokens), bool)
    x = np.asarray(w['embed'])[tokens]
    for i, kind in enumerate(cfg['layer_types']):
        lw = {k.split('.', 1)[1]: v for k, v in w.items()
              if k.startswith('l%d.' % i)}
        n = np.asarray(ref.layer_norm(x, lw['ln_g'], cfg['layer_norm_eps']),
                       'float64')
        score = 1 / (1 + np.exp(-n @ np.asarray(lw['router_w'], 'float64')))
        rank = np.argsort(-score, -1)
        at = np.arange(len(tokens))
        margin = score[at, rank[:, 1]] - score[at, rank[:, 2]]
        # the test's own reading has to be clear of the margin's edge
        assert (np.abs(margin - 0.02) > 1e-5).all()
        near |= (margin < 0.02) & np.asarray(
            [int(a) in held or int(b) in held
             for a, b in zip(rank[:, 1], rank[:, 2])])
        x = np.asarray(ref.layer(x, lw, ref._cfg_key(cfg),
                                 tuple(cfg['held_experts']), kind, 0.0,
                                 None)[0])
    rows = near[len(prompt) - 1:len(tokens) - 1]
    assert 0 < rows.sum() < len(rows)
    assert (plain != 0).any(-1).all()
    assert ((got == 0).all(-1) == rows).all()
    assert (got[~rows] == plain[~rows]).all()


def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's shares test: one layer, 16 query heads on 8 KV heads
    and 8 experts cut into eight shares (2 query heads on their KV head,
    one expert each). The eight shares' ``attn + routed``, with ``x`` and
    the shared experts counted once, equal the uncut reference layer."""
    import jax.numpy as jnp
    base = _cfg(num_attention_heads=16, num_key_value_heads=8, head_dim=8,
                num_hidden_layers=1, layer_types=['sliding_attention'],
                held_experts=list(range(8)), num_experts=8)
    w = _weights(base, seed=9)
    tokens = np.asarray(_tokens(20, 7), 'int32')
    x = np.asarray(w['embed'])[tokens]
    lw = {k.split('.', 1)[1]: v for k, v in w.items() if k.startswith('l0.')}
    want = np.asarray(ref.layer(
        jnp.asarray(x), lw, ref._cfg_key(base), tuple(range(8)),
        'sliding_attention', 0.0, None)[0])
    total, once = np.zeros_like(x), None
    for s in range(8):
        cfg = dict(base, num_attention_heads=2, num_key_value_heads=1,
                   held_experts=[s], num_experts=1)
        sw = dict(w)
        for name, cols in (('q_w', 16), ('k_w', 8), ('v_w', 8)):
            sw['l0.' + name] = w['l0.' + name][:, s * cols:(s + 1) * cols]
        sw['l0.o_w'] = w['l0.o_w'][s * 16:(s + 1) * 16]
        for name in ('w1', 'w3', 'w2'):
            sw['l0.' + name] = w['l0.' + name][s:s + 1]
        model = _model(cfg)
        p = systems.program_params(sw)
        y, _ = model._sequence_pass(p, jnp.asarray(tokens), 20)
        n = model._ln(jnp.asarray(x), p['l0_ln_g'])
        shared = np.asarray(model._shared(
            lambda name: p['l0_' + name], n))
        once = x + shared
        total += np.asarray(y) - once
    assert np.abs(total + once - want).max() < TOL


# ---------------------------------------------------------------------------
# two kinds of layer in one cache manager
# ---------------------------------------------------------------------------

def test_window_layers_pools_are_sized_by_the_window(toy):
    cfg, _, prog = toy
    # window 8, page 4: a ring of ceil(8/4) + 1 = 3 pages a sequence
    assert prog.window_pages == 3 and prog.max_pages == 16
    assert prog.window_pool_pages == 4 * 3 + 1
    assert prog.pages == 4 * 16 + 1
    row = 2 * 16 * 4                       # kv_heads x head_dim x float32
    full = 2 * 1 * 65 * 4 * row            # K and V of the one full layer
    window = 2 * 3 * 13 * 4 * row          # of the three sliding layers
    assert prog.cache_bytes() == full + window
    # had the sliding layers kept every page: four layers of 65 pages
    assert prog.cache_bytes() < 2 * 4 * 65 * 4 * row
    assert prog.per_sequence_bytes(64) == 2 * (16 + 3 * 3) * 4 * row
    pool = prog.new_cache()
    assert pool['l3_k'].shape == (65, 4, 32)
    assert pool['l0_k'].shape == (13, 4, 32)


def test_prefill_then_decode_through_a_wrapping_ring_equals_the_reference(
        toy):
    """Program level, logits compared: a prompt of 19 tokens (longer than
    the ring holds: pages 0 and 1 are never written for a sliding layer)
    and 30 steps that wrap the 3-page ring three times."""
    cfg, w, prog = toy
    prompt = _tokens(19, 11)
    ps, ring = 4, prog.window_pages
    pool = prog.new_cache()
    full = list(range(1, 6))                            # 5 pages
    wids = [0, 0, 1, 2, 3]                              # the last three
    table = np.zeros(16, 'int32')
    table[:5] = full
    wtable = np.zeros(ring, 'int32')
    for lp, page in enumerate(wids):
        if page:
            wtable[lp % ring] = page
    pool, tok, logits = prog.run_prefill(pool, prompt,
                                         {'full': full, 'window': wids})
    seq = list(prompt)
    want = _ref_logits(cfg, w, seq)[-1]
    assert np.abs(logits - want).max() < TOL and tok == int(want.argmax())
    free_full, free_ring = list(range(6, 30)), [4, 5, 6, 7, 8]
    for _ in range(30):
        pos = len(seq)
        seq.append(tok)
        if pos % ps == 0:                   # a new page, of both kinds
            table[pos // ps] = free_full.pop(0)
            col = (pos // ps) % ring
            free_ring.append(int(wtable[col]))      # the page behind
            wtable[col] = free_ring.pop(0)
        tokens, positions = np.zeros(4, 'int32'), np.zeros(4, 'int32')
        tables = np.zeros((4, 16), 'int32')
        wtables = np.zeros((4, ring), 'int32')
        tokens[2], positions[2] = tok, pos
        tables[2], wtables[2] = table, wtable
        pool, toks, logits = prog.run_step(
            pool, tokens, positions, {'full': tables, 'window': wtables})
        want = _ref_logits(cfg, w, seq)[-1]
        assert np.abs(logits[2] - want).max() < TOL, pos
        tok = int(toks[2])
        assert tok == int(want.argmax())
    assert tuple(prog.last_step_stats) == Cohere2MoELM.step_stats
    # one live slot, four layers, top-2: 8 assignments, some of them
    # here, at most 1 on any one expert of any one layer
    assert prog.last_step_stats['moe_assignments'] == 8
    assert 0 <= prog.last_step_stats['moe_assignments_here'] <= 8
    assert prog.last_step_stats['moe_expert_load_max'] <= 1


def _served_equal_reference(cfg, w, prompts, outs):
    for prompt, out in zip(prompts, outs):
        lg = ref.next_token_logits(cfg, w, [prompt], [out])[0]
        assert [int(r.argmax()) for r in lg] == list(out)


def test_engine_gives_pages_back_and_counts_them(toy):
    cfg, w, prog = toy
    released = spans.phase_histogram('eng.tick.release_window')
    before = released.count
    eng = DecodeEngine(prog, max_new_tokens=32, prefill_interleave=2,
                       prefix_cache=False)
    try:
        prompts = [_tokens(n, 20 + n) for n in (5, 19, 30, 12, 9, 27)]
        streams = [eng.generate(p, max_new_tokens=30) for p in prompts]
        outs = [s.result(timeout=120) for s in streams]
        _served_equal_reference(cfg, w, prompts, outs)
        stats = eng.stats()
        counts = stats['counts']
        # every sequence ran past the window (8) and wrapped its ring
        assert counts['window_pages_released'] >= 6 * 5
        assert counts['pages_live.full'] == 0
        assert counts['pages_live.window'] == 0
        assert stats['pages']['pages_free'] == 64
        assert stats['pages_window']['pages_free'] == 12
        assert counts['moe_assignments'] == \
            (counts['tokens'] - counts['prefills']) * 2 * 4
        # 4 of 8 experts are held: about half the assignments land here
        share = counts['moe_assignments_here'] / counts['moe_assignments']
        assert 0.3 < share < 0.7
        assert counts['steps'] <= counts['moe_expert_load_max'] \
            <= counts['moe_assignments_here']
        assert counts['pool_exhausted'] == 0
    finally:
        eng.close()
    assert released.count > before          # the host span was opened
    assert 'eng.tick.release_window' in spans.PHASES
    assert all(v == 1 for v in prog.trace_counts.values())


def test_prefix_hit_and_copy_on_write_on_both_kinds_of_layer(toy):
    cfg, w, prog = toy
    eng = DecodeEngine(prog, max_new_tokens=32, prefill_interleave=1,
                       prefix_cache=True)
    try:
        head = _tokens(8, 40)                           # two full pages
        longer = head + _tokens(4, 41)                  # three: the ring
        first = eng.generate(longer, max_new_tokens=12).result(timeout=120)
        # its first two pages again: a hit leaves one token to step on,
        # which is written into the second page. The registry holds that
        # page and a third chains through it, so it is copied first, in
        # the full layers' pool and in the sliding layers'
        second = eng.generate(head, max_new_tokens=12).result(timeout=120)
        counts = eng.stats()['counts']
        assert counts['prefix_hits'] == 1
        assert counts['prefix_tokens_saved'] == 7
        assert counts['cow_copies'] == 2
        # the whole prompt again: all three pages of both kinds are hit
        third = eng.generate(longer, max_new_tokens=12).result(timeout=120)
        counts = eng.stats()['counts']
        assert counts['prefix_hits'] == 2
        assert counts['prefix_tokens_saved'] == 7 + 11
        assert third == first
        _served_equal_reference(cfg, w, [longer, head], [first, second])
        # a prompt the ring cannot hold whole registers nothing for the
        # sliding layers, so it can never be hit: 19 tokens = 5 pages > 3
        long_prompt = _tokens(19, 42)
        entries = eng.stats()['pages_window']['prefix_entries']
        a = eng.generate(long_prompt, max_new_tokens=6).result(timeout=120)
        assert eng.stats()['pages_window']['prefix_entries'] == entries
        hits = eng.stats()['counts']['prefix_hits']
        b = eng.generate(long_prompt, max_new_tokens=6).result(timeout=120)
        assert eng.stats()['counts']['prefix_hits'] == hits and a == b
    finally:
        eng.close()


def test_lru_eviction_order_of_the_prefix_registry():
    from mxnet_tpu.serving.decode.paged import PageAllocator, PrefixCache
    alloc = PageAllocator(8)
    pc = PrefixCache(2, alloc)
    a, b = alloc.alloc(2), alloc.alloc(2)
    pc.register([1, 2, 3, 4], a)
    pc.register([5, 6, 7, 8], b)
    pc.lookup([1, 2, 3, 4])                  # the first chain is newer now
    for page in a + b:
        alloc.release(page)                  # the registry's holds remain
    alloc.alloc(3)
    assert pc.evict_lru(2) == [b[1], b[0]]   # leaf first, then its parent
    assert pc.lookup([1, 2, 3, 4])[1] == 4


# ---------------------------------------------------------------------------
# what the family does not implement, scopes, the one-kind case
# ---------------------------------------------------------------------------

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
        lambda: model.paged_verify(params, None, None, None, None)]
    for case in cases:
        with pytest.raises(FamilyUnsupported) as err:
            case()
        assert err.value.family == 'cohere2_moe'
        assert 'cohere2_moe' in str(err.value)
    eng = DecodeEngine(prog, prefix_cache=False)
    try:
        with pytest.raises(FamilyUnsupported):
            eng.generate([1, 2, 3], prefill_only=True)
        with pytest.raises(FamilyUnsupported):
            eng.import_sequence({'prompt': [1], 'emitted': [], 'pos': 1})
    finally:
        eng.close()
    assert decode.model_from_config('cohere2_moe',
                                    systems.model_config(cfg)).family \
        == 'cohere2_moe'


def test_named_scopes_of_the_step_program(toy):
    _, _, prog = toy
    text = prog.compile_step().as_text()
    for i in range(4):
        for scope in ('attn', 'kv_gather', 'moe/router', 'moe/experts',
                      'moe/shared'):
            assert 'layer%d/%s' % (i, scope) in text, (i, scope)
    assert 'lm_head' in text and 'embed' in text


def test_one_kind_programs_are_what_they_were():
    """``TransformerLM`` is the one-kind case of the same code: program
    names, page counts, counters and zero retraces as before."""
    model, params = decode.init_transformer_lm(vocab=23, units=16, hidden=24,
                                               layers=2, heads=4, max_len=48)
    prog = PagedDecodeProgram(model, params, slots=4,
                              prefill_buckets=(4, 8, 24), page_size=8)
    assert prog.window_pages == 0 and prog.window_pool_pages == 0
    assert prog.pages == 4 * 6 + 1 and prog.max_pages == 6
    assert prog.cache_bytes() == 2 * 2 * 25 * 8 * 16 * 4
    assert 'window_pages' not in prog._manifest_extra()
    eng = DecodeEngine(prog, max_new_tokens=8)
    try:
        prompts = ([5, 11, 7], [3, 1, 4, 1, 5], [9, 9], list(range(1, 21)))
        outs = [eng.generate(p, max_new_tokens=8).result(timeout=60)
                for p in prompts]
        for prompt, out in zip(prompts, outs):
            logits = np.asarray(model.full_forward(
                params, np.asarray([prompt + out], 'int32')))[0]
            assert [int(r.argmax()) for r in
                    logits[len(prompt) - 1:-1]] == out
        stats = eng.stats()
        new = {'window_pages_released', 'moe_assignments',
               'moe_assignments_here', 'moe_expert_load_max',
               'pages_live.full', 'pages_live.window'}
        assert not new & set(stats['counts']) and 'pages_window' not in stats
        # what stays once all have retired: the 20-token prompt's two full
        # pages, held by the prefix registry (every partial tail page went
        # back with its owner's first write into it)
        assert stats['pages']['pages_used'] == 2
        assert stats['pages']['prefix_entries'] == 2
    finally:
        eng.close()
    assert sorted(prog.trace_counts) == ['prefill:24', 'prefill:4',
                                         'prefill:8', 'step']
    assert all(v == 1 for v in prog.trace_counts.values())
    names = {k: prog._compiled[k].as_text().split('HloModule ')[1]
             .split(',')[0].split(' ')[0] for k in prog._compiled}
    assert names['step'] == 'jit_fn_step'
    assert names['prefill:4'] == 'jit_prefill_b4'


_COUNTS = ['adapter_rejects', 'cow_copies', 'drain_timeouts',
           'fallback_tokens', 'handoff_pages', 'kv_page_copies',
           'kv_pages_view', 'kv_pages_walked', 'migrated_in', 'migrated_out',
           'page_evictions', 'pool_exhausted', 'prefill_exports', 'prefills',
           'prefix_hits', 'prefix_tokens_saved', 'rejected', 'requests',
           'retired', 'sampled_steps', 'sampled_tokens', 'spec_accepted',
           'spec_proposed', 'spec_rounds', 'steps', 'timeouts', 'tokens']
_POOL = ['occupancy_pct', 'pages_free', 'pages_total', 'pages_used',
         'prefix_entries']
_ACCOUNTING = ['cache_bytes', 'max_concurrent_sequences_per_gb',
               'page_bytes', 'paged', 'per_sequence_bytes_amortized',
               'per_sequence_bytes_max', 'pool']


@pytest.mark.parametrize('family', ['transformer', 'cohere2_moe'])
def test_what_the_benchmark_and_status_read_keeps_its_names(family, toy):
    """The names that readers outside the package match on, written out
    from the output of the commit before the page owner (PR 32): the
    counters, the pool blocks and the cache accounting of ``stats()``,
    and the XLA modules' names (``benchmark/`` tells the step, the
    prefills and the page copy apart by them)."""
    if family == 'transformer':
        model, params = decode.init_transformer_lm(
            vocab=23, units=16, hidden=24, layers=2, heads=4, max_len=48)
        prog = PagedDecodeProgram(model, params, slots=4,
                                  prefill_buckets=(4, 8, 24), page_size=8)
        counts, window = _COUNTS, None
    else:
        prog = toy[2]
        counts = sorted(_COUNTS + list(Cohere2MoELM.step_stats) + [
            'pages_live.full', 'pages_live.window',
            'window_pages_released'])
        window = _POOL
    prog.warmup()
    eng = DecodeEngine(prog, max_new_tokens=4)
    try:
        eng.generate([1, 2, 3, 4, 5], max_new_tokens=4).result(timeout=60)
        stats = eng.stats()
        assert sorted(stats['counts']) == counts
        assert sorted(stats['pages']) == _POOL
        assert (sorted(stats['pages_window']) if window
                else stats.get('pages_window')) == window
        assert sorted(eng.cache_accounting()) == _ACCOUNTING
        assert sorted(stats['cache']['pool']) == _POOL[:-1]
    finally:
        eng.close()
    names = {k: prog._compiled[k].as_text().split('HloModule ')[1]
             .split(',')[0].split(' ')[0] for k in prog._compiled}
    assert names.pop('step') == 'jit_fn_step'
    assert names.pop('copy') == 'jit_page_copy'
    assert names and all(name == 'jit_prefill_b%s' % key.split(':')[1]
                         for key, name in names.items())


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def test_flops_against_a_hand_computed_layer():
    with open(os.path.join(bench_tiny_cmda.bench_tiny.REPO, 'benchmark',
                           'configs', 'command-a-plus-4l-1of8.json')) as f:
        cfg = json.load(f)
    h = 4096
    attention = h * 2048 + 2 * h * 128 + 2048 * h       # Wq, Wk, Wv, Wo
    expert = 3 * h * 4096
    assert attention == 17_825_792 and expert == 50_331_648
    # a token: attention, the 128-wide router, 4 shared and 8 x 16/128 = 1
    # routed expert on average
    assert flops.layer_matmul_params(cfg) == \
        attention + h * 128 + (4 + 1) * expert
    # a layer holds 16 + 4 experts, attention, router and one norm
    assert flops.layer_params(cfg) == \
        attention + h * 128 + h + 20 * expert == 1_024_987_136
    # 128 sequences of 6000 cached tokens: the full layer sees 6000, each
    # of the three sliding layers 4096
    seen = 128 * (6000 + 3 * 4096)
    ops, byts = flops.decode_step(cfg, 128, 128 * 6000)
    assert ops == 2 * 128 * (4 * flops.layer_matmul_params(cfg)
                             + h * 32768) + 4 * 2048 * seen
    assert byts == 2 * (4 * 1_024_987_136 + 32768 * h + h) \
        + 2 * 128 * 2 * seen
    # short sequences: the window caps nothing
    ops_s, byts_s = flops.decode_step(cfg, 128, 128 * 1000)
    assert byts_s - 2 * (4 * 1_024_987_136 + 32768 * h + h) \
        == 512 * 4 * 128 * 1000
    # the serve path: never more than every position through everything
    with open(os.path.join(bench_tiny_cmda.bench_tiny.REPO, 'benchmark',
                           'traffic', 'mixed-saturated.json')) as f:
        traffic = json.load(f)
    per_token = flops.serve_flops_per_token(cfg, traffic)
    dense = 2 * (4 * flops.layer_matmul_params(cfg) + h * 32768)
    assert dense < per_token < dense * (1595.2 + 144.8) / 144.8 * 1.3
