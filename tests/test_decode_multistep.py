"""Several decode steps in one call of the paged step program
(``PagedDecodeProgram.run_steps``) and the scheduler's rule for how many
(``engine._RunLength``), on the CPU rig at toy sizes: the dense decoder,
Granite's hybrid (recurrent state carried through the run) and Xing4's
expert family (device-side counts summed over the run).

A run of n steps feeds each step the token the one before it produced, so
its streams are the streams of n single steps, bit for bit: greedy and
sampled (a key a position), through a prefix hit still extending, and up
to a finish in the middle of the run. What a run writes lands in its
slots' private pages and, past a slot's budget, in the trash page only.
That the compiled run carries the pool and the recurrent state in place,
with no copy of either, is a property of the program compiled for the
chip: ``tests/test_tpu_aot_compile.py`` reads it there.
"""
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                'benchmark_tests'))

import bench_tiny_g4h  # noqa: E402
import bench_tiny_x4  # noqa: E402

from benchmark.reference import granitemoehybrid as g4h_ref  # noqa: E402
from benchmark.reference import xing4_0 as x4_ref  # noqa: E402
from benchmark.systems import granitemoehybrid as g4h_sys  # noqa: E402
from benchmark.systems import xing4_0 as x4_sys  # noqa: E402
from mxnet_tpu.serving.decode import (DecodeEngine,  # noqa: E402
                                      DecodeProgram, GraniteHybridLM,
                                      PagedDecodeProgram,
                                      init_cohere2_moe_lm,
                                      init_transformer_lm)
from mxnet_tpu.serving.decode.engine import _RunLength  # noqa: E402
from mxnet_tpu.serving.decode.paged import (TRASH_PAGE,  # noqa: E402
                                            PageOwner)
from mxnet_tpu.serving.decode.xing4 import Xing4LM  # noqa: E402


def _dense(**kw):
    model, params = init_transformer_lm(vocab=23, units=16, hidden=24,
                                        layers=2, heads=4, max_len=48,
                                        seed=0)
    kw = dict(dict(slots=3, prefill_buckets=(4, 8, 16, 32), page_size=8,
                   emit_logits=False), **kw)
    return PagedDecodeProgram(model, params, **kw)


def _granite(**kw):
    cfg = bench_tiny_g4h.config()
    w = {k: v.astype('float32')
         for k, v in g4h_ref.make_weights(cfg, 3).items()}
    kw = dict(dict(slots=4, prefill_buckets=[8, 16, 32], page_size=4,
                   emit_logits=False), **kw)
    return PagedDecodeProgram(GraniteHybridLM(g4h_sys.model_config(cfg)),
                              g4h_sys.program_params(w), **kw)


def _xing4(**kw):
    cfg = bench_tiny_x4.config()
    w = {k: v.astype('float32')
         for k, v in x4_ref.make_weights(cfg, 3).items()}
    kw = dict(dict(slots=4, prefill_buckets=[8, 16, 32], page_size=4,
                   emit_logits=False), **kw)
    return PagedDecodeProgram(Xing4LM(x4_sys.model_config(cfg)),
                              x4_sys.program_params(w), **kw)


FAMILIES = {'dense': _dense, 'granite': _granite, 'moe': _xing4}


def _serve(prog, requests, n, **engine):
    """``requests`` ``[(prompt, max_new, generate kwargs)]`` through one
    engine whose every call runs ``n`` steps: (token lists, finish
    reasons, counts)."""
    eng = DecodeEngine(prog, timeout_s=120.0, max_queue=32, **engine)
    eng._runs.steps = lambda *tick: n
    try:
        streams = [eng.generate(p, max_new_tokens=m, **kw)
                   for p, m, kw in requests]
        outs = [s.result(120) for s in streams]
        reasons = [s.finish_reason for s in streams]
        counts = eng.stats()['counts']
    finally:
        eng.close()
    return outs, reasons, counts


def _greedy(prog, prompt, n):
    import jax.numpy as jnp
    toks, out = list(prompt), []
    for _ in range(n):
        logits = np.asarray(prog.model.full_forward(
            prog._params, jnp.asarray([toks], 'int32')))
        out.append(int(logits[0, -1].argmax()))
        toks.append(out[-1])
    return out


def _requests(case, vocab):
    rng = np.random.RandomState(5)
    lengths = ((5, 9), (11, 6), (3, 13), (7, 4), (9, 10))
    if case == 'sampled':
        return [(list(rng.randint(1, vocab, n)), m,
                 dict(temperature=0.9, top_p=0.9, seed=11 + i))
                for i, (n, m) in enumerate(lengths)]
    if case == 'prefix_hit':
        # two full pages, then a prompt that shares them and extends six
        # tokens through the step: its first run starts mid-extension
        head = list(rng.randint(1, vocab, 16))
        return [(head, 12, {}), (head + [3, 9, 4, 4, 2, 7], 9, {}),
                (head + [5], 5, {})]
    return [(list(rng.randint(1, vocab, n)), m, {}) for n, m in lengths]


@pytest.mark.parametrize('case', ['dense', 'granite', 'moe', 'sampled',
                                  'prefix_hit'])
def test_a_run_of_steps_streams_what_single_steps_stream(case):
    prog = FAMILIES.get(case, _dense)()
    requests = _requests(case, prog.model.vocab)
    one, _, c1 = _serve(prog, requests, 1)
    four, _, c4 = _serve(prog, requests, 4)
    assert four == one
    assert [len(o) for o in four] == [m for _, m, _ in requests]
    assert c1['multi_step_calls'] == 0 and c1['steps'] == c1['step_calls']
    assert c4['multi_step_calls'] == c4['step_calls'] > 0
    assert c4['steps'] == 4 * c4['step_calls']
    if case == 'prefix_hit':
        assert c1['prefix_hits'] == c4['prefix_hits'] == 2
        for (prompt, m, _), out in zip(requests, four):
            assert out == _greedy(prog, prompt, m)
    if case == 'sampled':
        assert c4['sampled_tokens'] == sum(m for _, m, _ in requests)


@pytest.mark.parametrize('family', ['granite', 'moe'])
def test_a_runs_device_counts_are_its_steps_summed(family):
    """One sequence of nine tokens: eight steps either way (a prefill's
    token, then two runs of four), so the family's counts agree."""
    prog = FAMILIES[family]()
    request = [(list(range(1, 8)), 9, {})]
    one, _, c1 = _serve(prog, request, 1)
    four, _, c4 = _serve(prog, request, 4)
    assert four == one
    assert c1['steps'] == c4['steps'] == 8 and c4['step_calls'] == 2
    for name in prog.model.step_stats:
        assert c4[name] == c1[name], name
    assert c4['moe_assignments'] > 0


@pytest.mark.parametrize('how', ['max_new', 'eos', 'max_len'])
def test_a_slot_that_finishes_mid_run(how):
    """Each token's finish checks run at its own position; what the run
    computed for the slot after its finish is dropped and booked in
    ``slot_steps_idle``."""
    prog = _dense()
    prompt, max_new, extra = [5, 3, 1, 7, 2, 9], 7, {}
    if how == 'max_len':
        prompt, max_new = list(np.arange(30) % 21 + 1), 40
    if how == 'eos':
        prompt = [22, 3, 15, 6, 10]
        free, _, _ = _serve(prog, [(prompt, 20, {})], 1)
        # the first token that is new to the stream and not the last of
        # a run of four: tokens 2-5 come from the first run
        at = next(i for i, t in enumerate(free[0])
                  if i >= 2 and (i - 1) % 4 != 3 and t not in free[0][:i])
        max_new, extra = 20, {'eos_id': free[0][at]}
    request = [(prompt, max_new, extra)]
    one, r1, c1 = _serve(prog, request, 1)
    four, r4, c4 = _serve(prog, request, 4)
    assert four == one
    assert r4 == r1 == ['eos' if how == 'eos' else 'length']
    if how == 'max_len':
        assert len(four[0]) == 48 - len(prompt)
    # one sequence: a step that emitted nothing was a step past its end
    assert c4['slot_steps_idle'] == c4['steps'] - (len(four[0]) - 1) > 0
    assert c1['slot_steps_idle'] == 0
    if how == 'max_new':
        assert (c4['steps'], c4['step_calls'], c4['multi_step_calls'],
                c4['slot_steps_idle'], c4['tokens']) == (8, 2, 2, 2, 7)
        assert (c1['steps'], c1['step_calls'], c1['multi_step_calls'],
                c1['tokens']) == (6, 6, 0, 7)


def test_a_run_writes_only_its_slots_private_pages_and_the_trash_page():
    """Slot 0 holds a registered two-page prompt and steps four; slot 1
    shares both pages, writes another token at the prompt's last position
    in the second (copy on write through the run's lookahead) and stops
    after two steps; slot 2 is empty. Every page that changed is a page one of them
    holds alone, or the trash page: the shared pages are as the prefill
    left them."""
    prog = _dense()
    owner = PageOwner(prog.page_spec, prog.pool_pages, threading.Lock(),
                      True, {})
    pool = prog.new_cache()
    prompt = [int(t) for t in np.arange(16) % 19 + 2]
    a = owner.open(0)
    ids = owner.place(a, 16)
    pool, first, _ = prog.run_prefill(pool, prompt, ids)
    owner.register(prompt, ids)
    b = owner.open(1)
    assert owner.share_prefix(b, prompt) == (15, 2)

    def copy(src, dst):
        nonlocal pool
        pool = prog.run_copy_page(pool, src, dst)

    assert owner.make_writable(a, 16, 19, copy)
    assert owner.make_writable(b, 15, 16, copy)
    shared = set(ids)
    alone = (set(a.held['full']) | set(b.held['full'])) - shared
    assert len(alone) == 3          # a's third page, b's copy and third
    before = {k: np.asarray(v) for k, v in pool.items()}
    pool, toks, _ = prog.run_steps(
        pool, [first, prompt[15] + 1, 0], [16, 15, 0],
        owner.tables(3, [(0, a), (1, b)]), 4, budgets=[4, 2, 0])
    changed = {p for p in range(prog.pages)
               if any(not np.array_equal(before[k][p], np.asarray(v)[p])
                      for k, v in pool.items())}
    assert changed - alone <= {TRASH_PAGE}
    assert not changed & shared
    assert alone <= changed
    assert toks.shape == (4, 3)


class _Observed:
    """A rule that has seen ticks of ``host`` ms of host time against
    steps of ``step`` ms."""

    def __init__(self, host, step, max_run=4, ticks=20):
        self.rule = _RunLength(max_run)
        for _ in range(ticks):
            self.rule.observe(host * 1e-3, step * 1e-3)


@pytest.mark.parametrize('case', ['low_occupancy', 'host_under_step',
                                  'mask', 'logits', 'draft', 'ring',
                                  'saturated'])
def test_the_rule_runs_one_step_unless_saturated_and_host_bound(case):
    """n = 4 only where both hold: the tick's admissions leave no slot
    free or a request waiting, and the host's time a tick is at least the
    device's a step; and never where a call would lose what is one
    step's (a mask, logits), a draft proposes, or a ring gives pages
    back."""
    live, pending, free = 128, 40, 0          # saturated
    if case == 'low_occupancy':
        live, pending, free = 3, 0, 125
    host, step = (4.0, 15.8) if case == 'host_under_step' else (9.0, 3.35)
    max_run = 4
    if case in ('mask', 'logits', 'draft', 'ring'):
        if case == 'ring':
            model, params = init_cohere2_moe_lm(seed=0)
            prog = PagedDecodeProgram(model, params, slots=2,
                                      prefill_buckets=(8,), page_size=4,
                                      emit_logits=False)
        else:
            prog = _dense(logit_mask=case == 'mask',
                          emit_logits=case == 'logits',
                          spec_k=2 if case == 'draft' else 0)
        draft = None
        if case == 'draft':
            draft = DecodeProgram(prog.model, prog._params_np, slots=3,
                                  prefill_buckets=(4, 8, 16, 32))
        eng = DecodeEngine(prog, draft=draft)
        try:
            max_run = eng._runs.max_run
        finally:
            eng.close()
        assert max_run == 1
        assert prog.max_run == (4 if case == 'draft' else 1)
    seen = _Observed(host, step, max_run).rule
    n = seen.steps(live, pending, free, 8)
    assert n == (4 if case == 'saturated' else 1)
    if case == 'saturated':
        # admissions fill every slot, or leave a request waiting
        assert seen.steps(120, 8, 8, 8) == 4
        assert seen.steps(120, 40, 8, 2) == 4
        # every waiting request admitted, slots left free
        assert seen.steps(100, 3, 28, 8) == 1


def test_the_rule_holds_its_choice_between_its_two_thresholds():
    """Engaged once the host's time reaches the step's, held down to 3/4
    of it, let go below: n does not flap on a ratio near 1."""
    rule = _RunLength(4)
    rule.observe(0.9e-3, 1e-3)
    assert rule.steps(8, 4, 0, 1) == 1
    for _ in range(20):
        rule.observe(1.1e-3, 1e-3)
    assert rule.steps(8, 4, 0, 1) == 4
    for _ in range(20):
        rule.observe(0.8e-3, 1e-3)
    assert rule.steps(8, 4, 0, 1) == 4
    for _ in range(20):
        rule.observe(0.6e-3, 1e-3)
    assert rule.steps(8, 4, 0, 1) == 1
