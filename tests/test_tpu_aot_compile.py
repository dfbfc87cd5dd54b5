"""Decode steps compiled for a described TPU v5e, at published widths:
GPT-1's, and one Mamba-2 and one attention layer of Granite 4.0-H Small.

Nothing runs and no chip is needed: the TPU's compiler is installed here
and compiles for a chip that is described, not attached. What it shows is
what the CPU rig cannot. The one-token step placed on a TPU reads its K/V
through the page table inside one kernel an attention layer
(``ops.pallas.flash_paged_decode_attention``): no view of slots x
max_pages x page_size rows is gathered, copied or relaid, and the pools
are still updated in place. A program that still gathers (the speculative
verify's chunk) shows which layout the compiler gives the view: PR 31
measured on the chip that a head split of it (f32[128,512,12,64], minor
dimension 64 in tiles of 128 lanes) costs a relayout of both views in
every layer, 28 ms of a 60 ms step, and that a fill over the view hid
another (PERF.md section 6).

The programs choose the walk by where they are placed
(``paged.walks_pages``), so the fixtures trace them under
``jax.default_device`` of the described chip.

The Granite step shows what the recurrent state costs a step: every slot
entry of the donated cache is updated in place (aliased input to output),
one fusion reads the state, writes it and reduces it against C, and nothing
else of the state's size is allocated.

One file, one fixture, described inside the fixture: only one process may
load the TPU's library (see the ``on-chip-measurement`` guide).
"""
import re

import pytest

SLOTS, PAGE, PAGES, MAX_LEN = 128, 16, 4097, 512
UNITS, HEADS, LAYERS = 768, 12, 2
CHUNK = 4                                        # the verify's rows a slot


@pytest.fixture(scope='module')
def chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:                               # noqa: BLE001
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return topo.devices[0]


@pytest.fixture(scope='module')
def one_chip(chip):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(chip)


def _compiled(fn, chip, *avals):
    """``fn`` compiled for the described chip as the served program is:
    traced as placed there, the pool donated, the TPU's default matmul
    precision."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        with jax.default_matmul_precision('default'), \
                jax.default_device(chip):
            return jax.jit(fn, donate_argnums=(1,)).lower(*avals).compile()
    finally:
        jax.config.update('jax_enable_compilation_cache', cached)
        compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def gpt1(chip, one_chip):
    """``TransformerLM`` at 128 slots x 512 positions of 768 columns in 12
    heads (two layers, a small vocabulary) and the avals of its paged
    programs: (model, params, pool, i32)."""
    import jax
    from mxnet_tpu.serving.decode import TransformerLM
    from mxnet_tpu.serving.decode.paged import pool_avals
    model = TransformerLM(dict(vocab=1024, units=UNITS, hidden=4 * UNITS,
                               layers=LAYERS, heads=HEADS, max_len=MAX_LEN))

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, 'int32', sharding=one_chip)

    return (model, on(jax.eval_shape(lambda: model.init_params(0))),
            on(pool_avals(model.paged_spec(PAGE), PAGES)), i32)


@pytest.fixture(scope='module')
def step_text(chip, gpt1):
    """``TransformerLM.paged_step``: the one-token step, which walks."""
    model, params, pool, i32 = gpt1
    return _compiled(model.paged_step, chip, params, pool, i32(SLOTS),
                     i32(SLOTS), i32(SLOTS, MAX_LEN // PAGE)).as_text()


@pytest.fixture(scope='module')
def verify_text(chip, gpt1):
    """``TransformerLM.paged_verify``: ``CHUNK`` query rows a slot, which
    still gathers the view."""
    model, params, pool, i32 = gpt1
    return _compiled(model.paged_verify, chip, params, pool,
                     i32(SLOTS, CHUNK), i32(SLOTS),
                     i32(SLOTS, MAX_LEN // PAGE)).as_text()


def _results(text, shape):
    """Instructions whose result has ``shape`` (any type, any layout)."""
    return re.findall(r'^\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\['
                      + re.escape(shape) + r'\]', text, re.M)


def _arrays_of(text, elements, minor):
    """Shapes of every array of ``elements`` elements whose minor
    dimension is one of ``minor`` (a K/V row, or one head of it) that an
    instruction of ``text`` makes or takes: the gathered view, whatever
    it is reshaped, copied or split into."""
    out = set()
    for dims in re.findall(r'\b(?:f32|bf16|f16)\[([\d,]+)\]', text):
        sizes = [int(d) for d in dims.split(',')]
        n = 1
        for d in sizes:
            n *= d
        if n == elements and sizes[-1] in minor:
            out.add(dims)
    return sorted(out)


def _kernel_calls(text):
    return re.findall(r'custom_call_target="tpu_custom_call"', text)


def test_no_head_is_split_out_of_the_view(verify_text):
    assert not _results(verify_text, '%d,%d,%d,%d' % (
        SLOTS, MAX_LEN, HEADS, UNITS // HEADS))


def test_nothing_is_laid_over_the_gathered_view(verify_text):
    view = '%d,%d,%d,%d' % (SLOTS, MAX_LEN // PAGE, PAGE, UNITS)
    names = _results(verify_text, view)
    assert names, 'the view is gathered in this shape'
    assert not [n for n in names if 'select' in n or n.startswith('copy')]
    assert not re.search(r'\[%s\]\S* select\(' % re.escape(view),
                         verify_text)
    assert not _kernel_calls(verify_text)
    # the reading that finds no view in the step finds this one
    assert _arrays_of(verify_text, SLOTS * MAX_LEN * UNITS,
                      (UNITS, UNITS // HEADS))


def test_the_gpt1_step_walks_the_table_and_gathers_no_view(step_text):
    # no array of slots x max_pages x page_size rows, in any shape: no
    # kv_gather fusion, no copy of the view, no split of it
    assert not _arrays_of(step_text, SLOTS * MAX_LEN * UNITS,
                          (UNITS, UNITS // HEADS))
    assert 'kv_gather' not in step_text
    # one kernel an attention layer
    assert len(_kernel_calls(step_text)) == LAYERS
    assert step_text.count('mxnet_tpu_paged_decode_walk') >= LAYERS


def test_the_gpt1_step_updates_its_pools_in_place(step_text):
    aliases = re.search(r'input_output_alias=\{(.*?)\}, entry',
                        step_text).group(1)
    assert len(re.findall(r'\{\d+\}: \(\d+, \{\}, may-alias\)', aliases)) \
        == 2 * LAYERS
    # and nothing copies a pool on the way to the kernel that reads it
    pool = '%d,%d,%d' % (PAGES, PAGE, UNITS)
    assert not [n for n in _results(step_text, pool)
                if n.startswith('copy')]


def test_the_append_is_one_scatter_a_pool(step_text):
    assert len(re.findall(r' scatter\(', step_text)) == 2 * LAYERS
    pool = r'\[%d,%d,%d\]' % (PAGES, PAGE, UNITS)
    assert not re.search(pool + r'\S* dynamic-update-slice\(', step_text)


# ---------------------------------------------------------------------------
# the program's step: a run of up to ``MAX_RUN`` of these steps in a loop
# ---------------------------------------------------------------------------

def _run_program(model, slots, pages, chip, one_chip):
    """``PagedDecodeProgram``'s step program for ``model``, a loop over up
    to ``MAX_RUN`` steps, compiled for the described chip as the served
    program is: (text, memory analysis). The step function is a function
    of its operands alone, so the program is built without weights."""
    import jax
    from mxnet_tpu.serving.decode import PagedDecodeProgram

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, 'int32', sharding=one_chip)

    prog = PagedDecodeProgram(model, {}, slots=slots,
                              prefill_buckets=(64,), page_size=PAGE,
                              pages=pages, emit_logits=False)
    assert prog.max_run == 4
    compiled = _compiled(
        prog._paged_step_fn('step'), chip,
        on(jax.eval_shape(lambda: model.init_params(0))),
        on(prog._cache_avals()), i32(slots), i32(slots),
        i32(slots, prog.max_pages),
        {'n': i32(), 'budgets': i32(slots),
         'forced': i32(prog.max_run, slots)},
        on(prog._extra_avals('step')))
    return compiled.as_text(), compiled.memory_analysis()


@pytest.fixture(scope='module')
def gpt1_run(chip, one_chip, gpt1):
    return _run_program(gpt1[0], SLOTS, PAGES, chip, one_chip)


def _computations(text):
    """{computation name: its body} of a compiled module's text."""
    return dict(re.findall(r'^(?:ENTRY )?%?([\w.\-]+) .*?\{\n(.*?)^\}',
                           text, re.M | re.S))


def test_the_gpt1_run_loops_over_the_walking_step_in_place(gpt1_run):
    """One step straight and the run's loop, chosen by ``n``: the weights
    converted to bfloat16 ahead of the loop are the loop's branch's own, so
    a call of one step pays nothing for them."""
    text, memory = gpt1_run
    assert len(re.findall(r' while\(', text)) == 1
    assert len(_kernel_calls(text)) == 2 * LAYERS
    weights = r'= bf16\[(%d,%d|%d,%d)\]\S* convert\(' % (
        4 * UNITS, UNITS, UNITS, 4 * UNITS)
    converting = [name for name, body in _computations(text).items()
                  if re.search(weights, body)]
    entry = re.search(r'^ENTRY %?([\w.\-]+)', text, re.M).group(1)
    assert len(converting) == 1 and converting[0] != entry
    assert ' while(' in _computations(text)[converting[0]]
    assert 'kv_gather' not in text
    aliases = re.search(r'input_output_alias=\{(.*?)\}, entry',
                        text).group(1)
    assert len(re.findall(r'\{\d+\}: \(\d+, \{\}, may-alias\)', aliases)) \
        == 2 * LAYERS
    pool = '%d,%d,%d' % (PAGES, PAGE, UNITS)
    assert not [n for n in _results(text, pool) if n.startswith('copy')]
    assert not _arrays_of(text, SLOTS * MAX_LEN * UNITS,
                          (UNITS, UNITS // HEADS))
    # what the run allocates beside its operands is a step's, not a pool
    assert memory.temp_size_in_bytes < PAGES * PAGE * UNITS * 4


# ---------------------------------------------------------------------------
# granitemoehybrid: recurrent state in slot entries of the donated cache
# ---------------------------------------------------------------------------

G_SLOTS, G_MAX_LEN = 64, 512
G_STATE = (G_SLOTS, 128, 64, 128)                # float32, 268 MB


@pytest.fixture(scope='module')
def granite_step(chip, one_chip):
    """``GraniteHybridLM.paged_step`` at the published widths of one Mamba-2
    layer and one attention layer (hidden 4096, 128 heads x 64 x state 128,
    32 query heads on 8 KV heads of 128), 64 slots; two held experts and a
    small vocabulary keep the compile to seconds."""
    import jax
    from mxnet_tpu.serving.decode import GraniteHybridLM
    from mxnet_tpu.serving.decode.paged import pool_avals
    model = GraniteHybridLM(dict(
        vocab=1024, max_len=G_MAX_LEN, hidden=4096,
        layer_types=['mamba', 'attention'], eps=1e-5, head_dim=128,
        heads=32, kv_heads=8, mamba_heads=128, mamba_head_dim=64,
        mamba_state=128, mamba_conv=4, mamba_chunk=256, experts=72,
        held_experts=[0, 1], top_k=10, expert_hidden=768,
        shared_hidden=1536, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.0078125,
        logits_scaling=16.0))

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, 'int32', sharding=one_chip)

    spec = model.paged_spec(PAGE)
    params = on(jax.eval_shape(lambda: model.init_params(0)))
    pool = on(pool_avals(spec, G_SLOTS * spec.max_pages + 1, 0, G_SLOTS))
    compiled = _compiled(model.paged_step, chip, params, pool,
                         i32(G_SLOTS), i32(G_SLOTS),
                         i32(G_SLOTS, spec.max_pages))
    return spec, compiled.as_text(), compiled.memory_analysis()


def test_every_cache_entry_of_the_granite_step_is_updated_in_place(
        granite_step):
    spec, text, _memory = granite_step
    aliases = re.search(r'input_output_alias=\{(.*?)\}, entry', text).group(1)
    # outputs 0..3 are the cache entries, in the pytree's (sorted) order
    entries = sorted(list(spec.entries) + list(spec.slot_entries))
    assert entries == ['l0_conv', 'l0_ssm', 'l1_k', 'l1_v']
    assert len(re.findall(r'\{\d+\}: \(\d+, \{\}, may-alias\)', aliases)) \
        == len(entries)


def test_the_granite_step_makes_no_second_buffer_of_the_states_size(
        granite_step):
    _spec, text, memory = granite_step
    state = 4
    for d in G_STATE:
        state *= d
    assert memory.alias_size_in_bytes > state
    # what the step allocates beside its operands (the experts' products)
    # is less than one state entry
    assert memory.temp_size_in_bytes < state
    # one fusion reads the state, writes it and reduces it against C; no
    # copy of it is made
    shape = ','.join(str(d) for d in G_STATE)
    assert not [n for n in _results(text, shape) if n.startswith('copy')]
    out = r'f32\[%s\]\S*' % re.escape(shape)
    fused = re.findall(
        r'^\s*%?[\w.\-]+ = \(f32\[' + re.escape(shape.rsplit(',', 1)[0])
        + r'\]\S*, ' + out + r'\) fusion\(', text, re.M)
    assert len(fused) == 1, fused


def test_the_granite_step_walks_the_table_and_gathers_no_view(granite_step):
    spec, text, _memory = granite_step
    width = 8 * 128
    # no array of slots x max_pages x page_size rows of 8 KV heads x 128,
    # in any shape: neither the gathered view nor its copy for the split
    # into heads ([24576,8,8,128] at the served size)
    assert not _arrays_of(text, G_SLOTS * G_MAX_LEN * width, (width, 128))
    assert 'kv_gather' not in text
    # one kernel for the one attention layer, and its pools in place
    assert len(_kernel_calls(text)) == 1
    pool = '%d,%d,%d' % (G_SLOTS * spec.max_pages + 1, PAGE, width)
    assert not [n for n in _results(text, pool) if n.startswith('copy')]


def test_the_granite_run_carries_its_state_in_place(chip, one_chip,
                                                    granite_step):
    """The run's loop carries the 2.4 GB of state of the served cell (268
    MB here) and the pools: each updated in place, no copy of either, and
    nothing of the state's size allocated beside them."""
    spec = granite_step[0]
    from mxnet_tpu.serving.decode import GraniteHybridLM
    model = GraniteHybridLM(dict(
        vocab=1024, max_len=G_MAX_LEN, hidden=4096,
        layer_types=['mamba', 'attention'], eps=1e-5, head_dim=128,
        heads=32, kv_heads=8, mamba_heads=128, mamba_head_dim=64,
        mamba_state=128, mamba_conv=4, mamba_chunk=256, experts=72,
        held_experts=[0, 1], top_k=10, expert_hidden=768,
        shared_hidden=1536, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.0078125,
        logits_scaling=16.0))
    text, memory = _run_program(model, G_SLOTS,
                                G_SLOTS * spec.max_pages + 1, chip,
                                one_chip)
    assert len(re.findall(r' while\(', text)) == 1
    aliases = re.search(r'input_output_alias=\{(.*?)\}, entry', text).group(1)
    assert len(re.findall(r'\{\d+\}: \(\d+, \{\}, may-alias\)', aliases)) \
        == 4
    state = 4
    for d in G_STATE:
        state *= d
    shape = ','.join(str(d) for d in G_STATE)
    assert not [n for n in _results(text, shape) if n.startswith('copy')]
    pool = '%d,%d,%d' % (G_SLOTS * spec.max_pages + 1, PAGE, 8 * 128)
    assert not [n for n in _results(text, pool) if n.startswith('copy')]
    assert memory.temp_size_in_bytes < state


# ---------------------------------------------------------------------------
# xing4_0: latent rows, one pool a layer, keys and values from one copy
# ---------------------------------------------------------------------------

X_SLOTS, X_MAX_LEN, X_PAGES = 48, 17408, 4097
X_WIDTH, X_LATENT = 640, 512


@pytest.fixture(scope='module')
def xing4_step(chip, one_chip):
    """``Xing4LM.paged_step`` at the published widths of one dense and one
    expert layer (hidden 3584 in four streams, 32 heads of 128 + 64 on a
    latent of 512 + 64, queries of rank 768), 48 slots whose tables have
    the served 1088 columns; two held experts and a small vocabulary keep
    the compile to seconds."""
    import jax
    from mxnet_tpu.serving.decode.paged import pool_avals
    from mxnet_tpu.serving.decode.xing4 import Xing4LM
    model = Xing4LM(dict(
        vocab=1024, max_len=X_MAX_LEN, hidden=3584, layers=2,
        dense_layers=1, eps=1e-6, heads=32, q_rank=768, kv_rank=X_LATENT,
        nope_dim=128, rope_dim=64, v_dim=128, dense_hidden=9216,
        experts=64, held_experts=[0, 1], top_k=4, expert_hidden=1024,
        shared_hidden=1024, routed_scale=2.0, hc_mult=4, hc_iters=20,
        hc_eps=1e-6, hc_clamp=(-30.0, 30.0), rope_theta=10000.0,
        yarn=dict(factor=64, original_max=4096, beta_fast=32, beta_slow=1,
                  mscale=1, mscale_all_dim=1)))

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, 'int32', sharding=one_chip)

    spec = model.paged_spec(PAGE)
    params = on(jax.eval_shape(lambda: model.init_params(0)))
    pool = on(pool_avals(spec, X_PAGES))
    compiled = _compiled(model.paged_step, chip, params, pool,
                         i32(X_SLOTS), i32(X_SLOTS),
                         i32(X_SLOTS, spec.max_pages))
    return spec, compiled.as_text()


def test_the_xing4_step_walks_latent_pages_one_kernel_a_layer(xing4_step):
    spec, text = xing4_step
    assert spec.max_pages == 1088 and sorted(spec.entries) == ['l0_c',
                                                               'l1_c']
    # one kernel an attention layer, reading its one pool
    assert len(_kernel_calls(text)) == 2
    assert text.count('mxnet_tpu_paged_decode_walk') >= 2
    assert 'kv_gather' not in text
    # no array of slots x max_pages x page_size rows, latent or padded,
    # in any shape: neither a gathered view nor the values cut out of it
    assert not _arrays_of(text, X_SLOTS * X_MAX_LEN * X_WIDTH,
                          (X_WIDTH, X_LATENT))
    assert not _arrays_of(text, X_SLOTS * X_MAX_LEN * X_LATENT,
                          (X_LATENT, 128))


def test_the_xing4_step_makes_no_second_copy_of_a_latent_page(xing4_step):
    spec, text = xing4_step
    aliases = re.search(r'input_output_alias=\{(.*?)\}, entry', text).group(1)
    assert len(re.findall(r'\{\d+\}: \(\d+, \{\}, may-alias\)', aliases)) \
        == len(spec.entries)
    pool = '%d,%d,%d' % (X_PAGES, PAGE, X_WIDTH)
    assert not [n for n in _results(text, pool) if n.startswith('copy')]
    # nothing of a pool's size with the values' 512 columns: the values
    # are read out of the page's one VMEM copy, not sliced out in HBM
    assert not _results(text, '%d,%d,%d' % (X_PAGES, PAGE, X_LATENT))
    # the append is one scatter a pool
    assert len(re.findall(r' scatter\(', text)) == len(spec.entries)
    # the kernel takes six operands: table, positions, next live slot and
    # the chunks that are runs (prefetched scalars), the query rows, and
    # one pool (GPT-1's and Granite's take a K and a V pool: seven)
    calls = re.findall(r'custom-call\(([^)]*)\), custom_call_target='
                       r'"tpu_custom_call"', text)
    assert len(calls) == 2 and all(len(c.split(', ')) == 6 for c in calls), \
        calls
