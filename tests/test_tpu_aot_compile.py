"""The decode step compiled for a described TPU v5e, at GPT-1's widths.

Nothing runs and no chip is needed: the TPU's compiler is installed here
and compiles for a chip that is described, not attached. What it shows is
what the CPU rig cannot: which layout the compiler gives the gathered K/V
view. PR 31 measured on the chip that a head split of the view
(f32[128,512,12,64], minor dimension 64 in tiles of 128 lanes) costs a
relayout of both views in every layer, 28 ms of a 60 ms step, and that a
fill over the view hid another (PERF.md section 6).

One file, one fixture, described inside the fixture: only one process may
load the TPU's library (see the ``on-chip-measurement`` guide).
"""
import re

import pytest

SLOTS, PAGE, PAGES, MAX_LEN = 128, 16, 4097, 512
UNITS, HEADS, LAYERS = 768, 12, 2


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:                               # noqa: BLE001
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def step_text(one_chip):
    """``TransformerLM.paged_step`` at 128 slots x 512 positions of 768
    columns in 12 heads (two layers, a small vocabulary), compiled at the
    TPU's default matmul precision as the served program is."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
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

    params = on(jax.eval_shape(lambda: model.init_params(0)))
    pool = on(pool_avals(model.paged_spec(PAGE), PAGES))
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        with jax.default_matmul_precision('default'):
            return jax.jit(model.paged_step, donate_argnums=(1,)).lower(
                params, pool, i32(SLOTS), i32(SLOTS),
                i32(SLOTS, MAX_LEN // PAGE)).compile().as_text()
    finally:
        jax.config.update('jax_enable_compilation_cache', cached)
        compilation_cache.reset_cache()


def _results(text, shape):
    """Instructions whose result has ``shape`` (any type, any layout)."""
    return re.findall(r'^\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\['
                      + re.escape(shape) + r'\]', text, re.M)


def test_no_head_is_split_out_of_the_view(step_text):
    assert not _results(step_text, '%d,%d,%d,%d' % (
        SLOTS, MAX_LEN, HEADS, UNITS // HEADS))


def test_nothing_is_laid_over_the_gathered_view(step_text):
    view = '%d,%d,%d,%d' % (SLOTS, MAX_LEN // PAGE, PAGE, UNITS)
    names = _results(step_text, view)
    assert names, 'the view is gathered in this shape'
    assert not [n for n in names if 'select' in n or n.startswith('copy')]
    assert not re.search(r'\[%s\]\S* select\(' % re.escape(view), step_text)


def test_the_append_is_one_scatter_a_pool(step_text):
    assert len(re.findall(r' scatter\(', step_text)) == 2 * LAYERS
    pool = r'\[%d,%d,%d\]' % (PAGES, PAGE, UNITS)
    assert not re.search(pool + r'\S* dynamic-update-slice\(', step_text)
