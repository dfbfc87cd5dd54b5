"""Multi-adapter (LoRA) serving + sampling in the one compiled step
(docs/SERVING.md "Multi-adapter serving & sampling"): adapter
artifact digest gate, device pool refcount/LRU/typed exhaustion,
zero-retrace adapter switching proven via trace_counts, base-row and
temperature-0 byte-identity, chi-square of compiled sampled streams
against the uncompiled softmax reference, same-seed speculative ==
plain sampled bit-identity (coupled rejection sampling), per-adapter
prefix-cache isolation, and seqstate migration carrying adapter +
sampling state bit-identically."""
import json
import os

import numpy as np
import pytest

from mxnet_tpu.serving.adapters import (AdapterExhaustedError,
                                        AdapterPool, AdapterRegistry,
                                        AdapterSpec, init_adapter,
                                        load_adapter, save_adapter)
from mxnet_tpu.serving.batcher import BackpressureError
from mxnet_tpu.serving.decode import (DecodeEngine,
                                      init_transformer_lm)
from mxnet_tpu.serving.decode.program import freeze_decode
from mxnet_tpu.serving.decode.sampling import key_for, sample_tokens
from mxnet_tpu.serving.freeze import load_frozen

VOCAB = 23
PROMPT = [3, 5, 7, 11, 13]
RANK = 4


@pytest.fixture(scope='module')
def model_params():
    return init_transformer_lm(vocab=VOCAB, units=16, hidden=24,
                               layers=2, heads=4, max_len=96, seed=0)


@pytest.fixture(scope='module')
def adapter_dir(tmp_path_factory, model_params):
    model, _ = model_params
    root = tmp_path_factory.mktemp('adapters')
    for i in range(3):
        # scale 50: the random 0.05-std A/B product is tiny; the
        # effect tests need the delta to actually flip an argmax
        ad = init_adapter(model, rank=RANK, seed=100 + i, scale=50.0,
                          name='ad%d' % i)
        save_adapter(str(root / ('ad%d' % i)), ad)
    return str(root)


@pytest.fixture(scope='module')
def slot_extras(model_params):
    model, params = model_params
    return freeze_decode(model, params, slots=4,
                         prefill_buckets=(16,), paged=False,
                         adapter_rank=RANK,
                         adapter_slots=4)


@pytest.fixture(scope='module')
def slot_legacy(model_params):
    """The greedy continuation by the uncached whole-sequence forward:
    what a program compiled without the sampling operand emitted (there
    is no such program any more)."""
    model, params = model_params

    def continuation(prompt, n):
        seq = list(prompt)
        for _ in range(n):
            logits = np.asarray(model.full_forward(
                params, np.asarray([seq], 'int32')))[0]
            seq.append(int(logits[-1].argmax()))
        return seq[len(prompt):]
    return continuation


@pytest.fixture(scope='module')
def paged_prog(model_params):
    model, params = model_params
    return freeze_decode(model, params, slots=4,
                         prefill_buckets=(16,), paged=True,
                         page_size=8, pages=64, spec_k=3,
                         adapter_rank=RANK,
                         adapter_slots=4)


@pytest.fixture(scope='module')
def draft_prog():
    dm, dp = init_transformer_lm(vocab=VOCAB, units=16, hidden=16,
                                 layers=1, heads=2, max_len=96,
                                 seed=9)
    return freeze_decode(dm, dp, slots=4, prefill_buckets=(16,),
                         paged=False)


# ---------------------------------------------------------------------------
# artifact
# ---------------------------------------------------------------------------

def test_adapter_artifact_roundtrip_bit_exact(tmp_path, model_params):
    model, _ = model_params
    ad = init_adapter(model, rank=RANK, seed=1, scale=2.5,
                      name='round')
    path = save_adapter(str(tmp_path / 'round'), ad)
    back = load_adapter(path)
    assert back.digest == ad.digest
    assert back.rank == RANK and back.scale == 2.5
    for key, arr in ad.arrays.items():
        assert np.array_equal(back.arrays[key], arr)


def test_adapter_tampered_params_rejected_typed(tmp_path,
                                                model_params):
    model, _ = model_params
    ad = init_adapter(model, rank=RANK, seed=2, name='tamper')
    path = save_adapter(str(tmp_path / 'tamper'), ad)
    arrays = dict(load_adapter(path).arrays)
    key = sorted(arrays)[0]
    arrays[key] = arrays[key].copy()
    arrays[key].flat[0] += 1.0
    np.savez(os.path.join(path, 'params.npz'), **arrays)
    with pytest.raises(ValueError, match='digest'):
        load_adapter(path)


def test_adapter_tampered_manifest_rejected_typed(tmp_path,
                                                  model_params):
    model, _ = model_params
    ad = init_adapter(model, rank=RANK, seed=3, scale=2.5,
                      name='manif')
    path = save_adapter(str(tmp_path / 'manif'), ad)
    man = os.path.join(path, 'MANIFEST.json')
    with open(man) as f:
        doc = json.load(f)
    doc['scale'] = 9.5
    with open(man, 'w') as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match='digest'):
        load_adapter(path)


def test_load_frozen_dispatches_adapter_artifacts(tmp_path,
                                                  model_params):
    model, _ = model_params
    ad = init_adapter(model, rank=RANK, seed=4, name='dispatch')
    path = save_adapter(str(tmp_path / 'dispatch'), ad)
    back = load_frozen(path)
    assert back.digest == ad.digest
    assert back.name == 'dispatch'


# ---------------------------------------------------------------------------
# pool
# ---------------------------------------------------------------------------

def test_pool_dedup_refcount_lru_and_typed_exhaustion(model_params):
    model, _ = model_params
    pool = AdapterPool(AdapterSpec.for_model(model, rank=RANK,
                                             capacity=3))
    ads = [init_adapter(model, rank=RANK, seed=10 + i)
           for i in range(3)]
    i0 = pool.load(ads[0])
    assert i0 != 0, 'row 0 is the reserved base row'
    assert pool.load(ads[0]) == i0, 'same digest must dedup'
    assert pool.stats()['resident'] == 1
    pool.release(i0)                      # drop the dedup pin
    i1 = pool.load(ads[1])
    pool.release(i0)                      # unpin ads[0] entirely
    # pool full: the next load must LRU-evict the unpinned row
    i2 = pool.load(ads[2])
    assert i2 == i0
    assert pool.index_of(ads[0].digest) is None
    # every user row pinned -> typed backpressure, not a crash
    with pytest.raises(AdapterExhaustedError) as exc:
        pool.load(ads[0])
    assert isinstance(exc.value, BackpressureError)
    pool.release(i1)
    pool.release(i2)
    assert pool.load(ads[0]) in (i1, i2)


def test_registry_resolves_ids_and_rejects_unknown(model_params,
                                                   adapter_dir):
    model, _ = model_params
    reg = AdapterRegistry(
        AdapterPool(AdapterSpec.for_model(model, rank=RANK,
                                          capacity=4)),
        root=adapter_dir)
    idx = reg.acquire('ad0')
    assert idx != 0
    assert reg.acquire('base') == 0
    assert reg.acquire(None) == 0
    with pytest.raises(KeyError):
        reg.acquire('nope')
    reg.release(idx)


# ---------------------------------------------------------------------------
# one compiled step: identity + zero retraces
# ---------------------------------------------------------------------------

def test_temp0_and_base_byte_identical_to_legacy(slot_extras,
                                                 slot_legacy,
                                                 adapter_dir):
    ref = slot_legacy(PROMPT, 10)
    with DecodeEngine(slot_extras, adapters=adapter_dir,
                      name='t0-ext') as e2:
        assert list(e2.generate(PROMPT, max_new_tokens=10)) == ref
        assert list(e2.generate(PROMPT, max_new_tokens=10,
                                adapter='base')) == ref


def test_adapter_changes_stream_and_rows_are_isolated(slot_extras,
                                                      adapter_dir):
    with DecodeEngine(slot_extras, adapters=adapter_dir,
                      name='fx') as eng:
        base = list(eng.generate(PROMPT, max_new_tokens=8))
        a0 = list(eng.generate(PROMPT, max_new_tokens=8,
                               adapter='ad0'))
        a1 = list(eng.generate(PROMPT, max_new_tokens=8,
                               adapter='ad1'))
        again = list(eng.generate(PROMPT, max_new_tokens=8,
                                  adapter='ad0'))
    assert a0 != base, 'adapter had no effect'
    assert a0 != a1, 'two adapters produced one stream'
    assert a0 == again, 'same adapter must be deterministic'


def test_adapter_switch_and_sampling_zero_retraces(paged_prog,
                                                   draft_prog,
                                                   adapter_dir):
    with DecodeEngine(paged_prog, draft=draft_prog,
                      adapters=adapter_dir, name='zr') as eng:
        # warmup: touch every compiled path once
        list(eng.generate(PROMPT, max_new_tokens=5))
        list(eng.generate(PROMPT, max_new_tokens=5, temperature=0.8,
                          seed=1))
        list(eng.generate(PROMPT, max_new_tokens=5, adapter='ad0'))
        tc0 = dict(paged_prog.trace_counts)
        dtc0 = dict(draft_prog.trace_counts)
        for i in range(6):
            list(eng.generate([2 + i, 9, 4], max_new_tokens=8,
                              adapter='ad%d' % (i % 3),
                              temperature=0.5 if i % 2 else 0.0,
                              seed=i))
        assert dict(paged_prog.trace_counts) == tc0, \
            'adapter/sampling rotation retraced the target'
        assert dict(draft_prog.trace_counts) == dtc0, \
            'adapter/sampling rotation retraced the draft'
        assert eng.stats()['adapters']['resident'] == 3


def test_mismatched_registry_rejected_typed(paged_prog, model_params,
                                            adapter_dir):
    model, _ = model_params
    wrong = AdapterRegistry(
        AdapterPool(AdapterSpec.for_model(model, rank=RANK,
                                          capacity=2)),
        root=adapter_dir)
    with pytest.raises(ValueError, match='compiled'):
        DecodeEngine(paged_prog, adapters=wrong, name='bad')


def test_pool_exhaustion_at_admission_and_row_reuse(model_params,
                                                    adapter_dir):
    import time
    model, params = model_params
    tiny = freeze_decode(model, params, slots=4,
                         prefill_buckets=(16,), paged=True,
                         page_size=8, pages=64,
                         adapter_rank=RANK, adapter_slots=2)
    with DecodeEngine(tiny, adapters=adapter_dir, name='tiny') as eng:
        h1 = eng.generate([1, 2, 3], max_new_tokens=40,
                          adapter='ad0')
        time.sleep(0.3)       # let h1 pin the only user row
        h2 = eng.generate([1, 2, 4], max_new_tokens=4, adapter='ad1')
        with pytest.raises(AdapterExhaustedError):
            h2.result(30)
        assert isinstance(h2.exception(), BackpressureError)
        list(h1)
        # retired stream unpinned its row: ad1 now loads
        h3 = eng.generate([1, 2, 5], max_new_tokens=4, adapter='ad1')
        assert list(h3)


# ---------------------------------------------------------------------------
# sampling: determinism + distribution
# ---------------------------------------------------------------------------

def test_rnn_lm_samples_without_adapter_operand():
    """Regression: families without lora_targets (RNNLM) must still
    freeze: every program samples, and the extras closure only passes the adapter operand when an adapter_spec compiled
    in (RNNLM.prefill/step take no such argument)."""
    from mxnet_tpu.serving.decode import init_rnn_lm
    model, params = init_rnn_lm(vocab=VOCAB, embed=16, hidden=24,
                                layers=1, max_len=64, seed=3)
    prog = freeze_decode(model, params, slots=2,
                         prefill_buckets=(16,), paged=False)
    with DecodeEngine(prog, name='rnn-sample') as eng:
        greedy = list(eng.generate(PROMPT, max_new_tokens=6))
        a = list(eng.generate(PROMPT, max_new_tokens=6,
                              temperature=0.9, seed=11))
        b = list(eng.generate(PROMPT, max_new_tokens=6,
                              temperature=0.9, seed=11))
    assert len(greedy) == 6
    assert a == b


def test_sampled_streams_deterministic_per_seed(slot_extras,
                                                adapter_dir):
    with DecodeEngine(slot_extras, adapters=adapter_dir,
                      name='det') as eng:
        a = list(eng.generate(PROMPT, max_new_tokens=8,
                              temperature=0.8, top_p=0.9, seed=42))
        b = list(eng.generate(PROMPT, max_new_tokens=8,
                              temperature=0.8, top_p=0.9, seed=42))
        c = list(eng.generate(PROMPT, max_new_tokens=8,
                              temperature=0.8, top_p=0.9, seed=43))
    assert a == b
    assert a != c, 'different seeds produced one stream'


def test_key_for_is_pure_and_position_independent():
    k = key_for(7, 11)
    assert k.shape == (2,) and k.dtype == np.uint32
    assert np.array_equal(k, key_for(7, 11))
    assert not np.array_equal(k, key_for(7, 12))
    assert not np.array_equal(k, key_for(8, 11))


def chi2_threshold(df):
    # Wilson-Hilferty approximation of the chi-square 99.9% quantile
    # (keeps the gate scipy-free); exact values: df=22 -> 48.27
    z = 3.0902          # Phi^-1(0.999)
    return df * (1 - 2.0 / (9 * df) + z * (2.0 / (9 * df)) ** 0.5) ** 3


def test_first_sampled_token_chi_square_vs_reference(model_params,
                                                     slot_extras,
                                                     adapter_dir):
    import jax.numpy as jnp
    model, params = model_params
    temp, n_seeds = 1.0, 240
    # uncompiled reference distribution for the first emitted token
    dev = {k: jnp.asarray(v) for k, v in params.items()}
    logits = np.asarray(model.full_forward(
        dev, jnp.asarray([PROMPT], 'int32')))[0, -1]
    probs = np.exp(logits / temp - np.logaddexp.reduce(logits / temp))
    # compiled draws: one stream per seed, first token only
    counts = np.zeros(VOCAB)
    with DecodeEngine(slot_extras, adapters=adapter_dir,
                      name='chi') as eng:
        streams = [eng.generate(PROMPT, max_new_tokens=1,
                                temperature=temp, top_p=1.0, seed=s)
                   for s in range(n_seeds)]
        for s in streams:
            counts[s.result(60)[0]] += 1
    expected = probs * n_seeds
    # pool bins with tiny expectation into one (chi-square validity)
    keep = expected >= 1.0
    obs = np.append(counts[keep], counts[~keep].sum())
    exp = np.append(expected[keep], expected[~keep].sum())
    exp = np.maximum(exp, 1e-9)
    stat = float(((obs - exp) ** 2 / exp).sum())
    df = len(obs) - 1
    assert stat < chi2_threshold(df), \
        'chi-square %.1f over df=%d: compiled sampler does not ' \
        'match the softmax reference' % (stat, df)


def test_sampled_spec_equals_plain_same_seed(paged_prog, draft_prog,
                                             adapter_dir):
    with DecodeEngine(paged_prog, draft=draft_prog,
                      adapters=adapter_dir, name='spec') as spec, \
            DecodeEngine(paged_prog, adapters=adapter_dir,
                         name='plain') as plain:
        for i, kw in enumerate((
                {'temperature': 0.9, 'top_p': 0.85},
                {'temperature': 0.9, 'top_p': 0.85,
                 'adapter': 'ad1'},
                {'temperature': 0.6},
                {})):
            a = list(spec.generate([5, 6, 7], max_new_tokens=12,
                                   seed=77 + i, **kw))
            b = list(plain.generate([5, 6, 7], max_new_tokens=12,
                                    seed=77 + i, **kw))
            assert a == b, \
                'speculative and plain decoding diverged at ' \
                'seed %d (%r)' % (77 + i, kw)
        st = spec.stats()['spec']
        assert st['accepted'] > 0, \
            'coupling never accepted a draft token'


def test_sample_tokens_temp0_is_greedy_and_mask_hook_applies():
    rs = np.random.RandomState(0)
    logits = rs.randn(4, 9).astype('float32')
    temps = np.array([0.0, 0.0, 0.8, 0.8], 'float32')
    top_ps = np.ones(4, 'float32')
    keys = np.stack([key_for(1, p) for p in range(4)])
    out = np.asarray(sample_tokens(logits, temps, top_ps, keys))
    assert list(out[:2]) == list(logits[:2].argmax(-1))
    # additive mask: -inf on the argmax column forces another token
    masks = np.zeros_like(logits)
    masks[:, logits[0].argmax()] = -1e9
    out2 = np.asarray(sample_tokens(logits, temps, top_ps, keys,
                                    masks=masks))
    assert out2[0] != logits[0].argmax()


def _sample_tokens_parent(logits, temps, top_ps, keys, masks=None):
    """The sampler as it stood before the gate (argsort, gather,
    cumsum, scatter, argmax in vocabulary order): the plain reference
    ``sample_tokens`` has to match token for token."""
    import jax
    import jax.numpy as jnp
    logits = jnp.asarray(logits)
    if masks is not None:
        logits = logits + masks
    greedy = jnp.argmax(logits, axis=-1).astype('int32')
    temps = jnp.asarray(temps, 'float32')
    top_ps = jnp.asarray(top_ps, 'float32')
    safe_t = jnp.where(temps > 0, temps, 1.0)
    logp = jax.nn.log_softmax(logits / safe_t[:, None], axis=-1)
    probs = jnp.exp(logp)
    order = jnp.argsort(-probs, axis=-1)
    sorted_p = jnp.take_along_axis(probs, order, axis=-1)
    csum = jnp.cumsum(sorted_p, axis=-1)
    keep_sorted = (csum - sorted_p) < top_ps[:, None]
    rows = jnp.arange(logits.shape[0])[:, None]
    keep = jnp.zeros(logits.shape, bool).at[rows, order].set(keep_sorted)
    filtered = jnp.where(keep, logp, -jnp.inf)
    gumbel = jax.vmap(
        lambda k, shape=logits.shape[1:]: jax.random.gumbel(k, shape)
    )(jnp.asarray(keys, 'uint32'))
    sampled = jnp.argmax(filtered + gumbel, axis=-1).astype('int32')
    return jnp.where(temps > 0, sampled, greedy).astype('int32')


def _sampler_rows(shape, rows, seed):
    """Logits for the equivalence cases. ``tied``: a few levels only,
    so equal probabilities sit wherever the nucleus ends; the first
    row is flat (every entry tied), the second holds its maximum at
    five ids."""
    n, vocab = shape
    rs = np.random.RandomState(seed)
    if rows == 'random':
        return (3.0 * rs.randn(n, vocab)).astype('float32')
    logits = rs.randint(0, 4, size=shape).astype('float32')
    logits[0] = 1.0
    logits[1, rs.choice(vocab, 5, replace=False)] = 6.0
    return logits


_TEMPS = {'greedy': lambda n: np.zeros(n, 'float32'),
          'sampled': lambda n: np.linspace(0.3, 1.5, n).astype('float32'),
          'mixed': lambda n: np.where(np.arange(n) % 3 == 1, 0.8,
                                      0.0).astype('float32')}


@pytest.mark.parametrize('rows', ['random', 'tied'])
@pytest.mark.parametrize('with_masks', [False, True])
@pytest.mark.parametrize('batch', ['greedy', 'sampled', 'mixed'])
@pytest.mark.parametrize('top_p', [0.1, 0.5, 0.9, 1.0])
@pytest.mark.parametrize('shape', [(4, 9), (16, 4099)])
def test_sample_tokens_equals_parent(shape, top_p, batch, with_masks,
                                     rows):
    import jax
    n, vocab = shape
    logits = _sampler_rows(shape, rows, seed=vocab + int(10 * top_p))
    temps = _TEMPS[batch](n)
    top_ps = np.full(n, top_p, 'float32')
    keys = np.stack([key_for(5, p) for p in range(n)])
    masks = None
    if with_masks:
        # forbid each row's best id and every fourth id besides
        masks = np.zeros(shape, 'float32')
        masks[np.arange(n), logits.argmax(-1)] = -np.inf
        masks[:, ::4] = -1e9
    got = np.asarray(jax.jit(sample_tokens)(logits, temps, top_ps,
                                            keys, masks))
    want = np.asarray(jax.jit(_sample_tokens_parent)(
        logits, temps, top_ps, keys, masks))
    assert got.dtype == want.dtype == np.int32
    assert got.tolist() == want.tolist()
    if batch != 'greedy' and top_p == 1.0 and vocab == 4099:
        live = logits if masks is None else logits + masks
        assert (got != live.argmax(-1)).any(), 'nothing was sampled'


@pytest.mark.parametrize('top_p', [0.5, 1.0])
def test_sample_tokens_tied_scores_take_smallest_id(monkeypatch, top_p):
    """Equal perturbed scores: with the Gumbel noise held at zero the
    score is the log-probability, so tied logits tie the argmax, and
    the smallest vocabulary id among the kept maxima has to win as in
    the parent's argmax over the vocabulary order."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(jax.random, 'gumbel',
                        lambda key, shape: jnp.zeros(shape, 'float32'))
    logits = _sampler_rows((6, 37), 'tied', seed=3)
    temps = np.full(6, 0.7, 'float32')
    top_ps = np.full(6, top_p, 'float32')
    keys = np.zeros((6, 2), 'uint32')
    got = np.asarray(sample_tokens(logits, temps, top_ps, keys))
    want = np.asarray(_sample_tokens_parent(logits, temps, top_ps,
                                            keys))
    assert got.tolist() == want.tolist()
    assert got[0] == 0 and logits[1, got[1]] == 6.0


def _primitives(jaxpr, skip=()):
    """Names of every primitive in ``jaxpr`` and below, the bodies of
    the primitives in ``skip`` left out."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name in skip:
            continue
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple))
                        else [val]):
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    names.extend(_primitives(sub, skip))
    return names


@pytest.mark.parametrize('with_masks', [False, True])
def test_sample_tokens_pays_for_the_nucleus_inside_a_cond_only(
        with_masks):
    import jax
    n, vocab = 4, 9
    args = [np.zeros((n, vocab), 'float32'), np.zeros(n, 'float32'),
            np.ones(n, 'float32'), np.zeros((n, 2), 'uint32')]
    if with_masks:
        args.append(np.zeros((n, vocab), 'float32'))
    jaxpr = jax.make_jaxpr(sample_tokens)(*args).jaxpr
    outside = _primitives(jaxpr, skip=('cond',))
    assert outside.count('cond') == 1
    costly = [p for p in outside
              if p.startswith(('sort', 'gather', 'scatter', 'cumsum',
                               'exp', 'random', 'threefry'))]
    assert costly == [], costly
    # the branch that samples: one sort carries ids and scores along,
    # so nothing is gathered into that order or scattered out of it
    inside = _primitives(jaxpr)
    assert inside.count('sort') == 1
    assert not [p for p in inside
                if p.startswith(('gather', 'scatter'))]


def test_sampled_steps_counts_the_steps_that_held_a_sampler(
        slot_extras, paged_prog, draft_prog):
    for prog, kw in ((slot_extras, {}), (paged_prog, {}),
                     (paged_prog, {'draft': draft_prog})):
        with DecodeEngine(prog, name='ss', **kw) as eng:
            for s in [eng.generate([2 + i, 9, 4], max_new_tokens=6)
                      for i in range(3)]:
                list(s)
            c0 = eng.stats()['counts']
            assert c0['steps'] > 0 and c0['sampled_steps'] == 0
            # the sampler is admitted first and decodes longest, so
            # every step of this stretch holds it among greedy rows
            streams = [eng.generate(PROMPT, max_new_tokens=12,
                                    temperature=0.8, top_p=0.9,
                                    seed=3)]
            streams += [eng.generate([2 + i, 9, 4], max_new_tokens=5)
                        for i in range(2)]
            for s in streams:
                list(s)
            c1 = eng.stats()['counts']
            assert c1['steps'] > c0['steps']
            assert c1['sampled_steps'] == c1['steps'] - c0['steps']
            assert c1['sampled_tokens'] == 12


# ---------------------------------------------------------------------------
# prefix isolation + migration
# ---------------------------------------------------------------------------

def test_prefix_cache_namespaced_per_adapter():
    from mxnet_tpu.serving.decode import PageAllocator, PrefixCache
    alloc = PageAllocator(pages=16)
    cache = PrefixCache(page_size=4, allocator=alloc)
    cache.register(list(range(12)), alloc.alloc(3), namespace='ad0')
    assert cache.lookup(list(range(12)), namespace='ad1')[1] == 0
    assert cache.lookup(list(range(12)), namespace='ad0')[1] == 12
    assert cache.lookup(list(range(12)))[1] == 0


def test_cross_adapter_prefix_isolation_end_to_end(paged_prog,
                                                   adapter_dir):
    """The cross-adapter isolation regression: a warm prefix chain
    registered under one adapter must never splice its KV into a
    different adapter's (or the base model's) stream."""
    prompt = [(3 * i + 1) % VOCAB for i in range(12)]
    with DecodeEngine(paged_prog, adapters=adapter_dir,
                      name='iso-cold') as cold:
        want_base = list(cold.generate(prompt, max_new_tokens=8))
    with DecodeEngine(paged_prog, adapters=adapter_dir,
                      name='iso') as eng:
        a0 = list(eng.generate(prompt, max_new_tokens=8,
                               adapter='ad0'))
        a0_again = list(eng.generate(prompt, max_new_tokens=8,
                                     adapter='ad0'))
        base = list(eng.generate(prompt, max_new_tokens=8))
        counts = eng.stats()['counts']
    assert a0 == a0_again
    assert base == want_base, \
        'base stream after adapter traffic differs from a cold ' \
        'engine: the prefix cache leaked KV across adapters'
    assert counts['prefix_tokens_saved'] > 0, \
        'prefix cache never hit within one namespace'


def test_migration_carries_adapter_and_sampling_bit_identical(
        paged_prog, adapter_dir):
    src = DecodeEngine(paged_prog, adapters=adapter_dir, name='src')
    dst = DecodeEngine(paged_prog, adapters=adapter_dir, name='dst')
    try:
        ref = list(dst.generate([4, 4, 2, 9], max_new_tokens=16,
                                adapter='ad1', temperature=0.6,
                                seed=5))
        s = src.generate([4, 4, 2, 9], max_new_tokens=16,
                         adapter='ad1', temperature=0.6, seed=5)
        it = iter(s)
        first = [next(it) for _ in range(3)]
        payload = src.export_sequence(s)
        assert payload['adapter_id'] == 'ad1'
        assert payload['sampling'] == {'temperature': 0.6,
                                       'top_p': 1.0, 'seed': 5}
        cont = dst.import_sequence(payload)
        rest = list(cont)
        merged = list(cont.tokens)
        assert merged[:3] == first
        assert merged[-len(rest):] == rest if rest else True
        assert merged == ref, \
            'migrated sampled adapter stream is not bit-identical'
    finally:
        src.close()
        dst.close()


def test_import_without_adapter_support_rejected_typed(model_params,
                                                       paged_prog,
                                                       adapter_dir):
    from mxnet_tpu.serving.decode.seqstate import SeqStateError
    model, params = model_params
    plainprog = freeze_decode(model, params, slots=4,
                              prefill_buckets=(16,), paged=True,
                              page_size=8, pages=64)
    src = DecodeEngine(paged_prog, adapters=adapter_dir, name='xsrc')
    dst = DecodeEngine(plainprog, name='xdst')
    try:
        s = src.generate([4, 4, 2, 9], max_new_tokens=16,
                         adapter='ad0')
        it = iter(s)
        for _ in range(2):
            next(it)
        payload = src.export_sequence(s)
        with pytest.raises(SeqStateError):
            dst.import_sequence(payload)
        list(s)  # drain the source stream cleanly
    finally:
        src.close()
        dst.close()
