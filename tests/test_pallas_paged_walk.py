"""The paged decode walk (``ops.pallas.flash_paged_decode_attention``):
one kernel reads a slot's live pages from the pool through its page table.

Through the Pallas interpreter on the CPU rig: the same kernel logic Mosaic
compiles on a TPU (``tests/test_tpu_aot_compile.py`` compiles it there at
the served widths). Both head geometries the decode families have, at their
published widths: GPT-1's 12 heads on 12 groups of 64 float32 columns, and
Granite's 32 query heads on 8 groups of 128 bfloat16 columns. Equal to
``paged.gather_pages`` + a dense softmax over the rows a position has seen,
to the rounding of an online softmax; what lies beyond a position, in the
trash page or in a page of the pool no table names, changes no bit.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas import (flash_paged_decode_attention,
                                  paged_walk_fits)
from mxnet_tpu.serving.decode.paged import TRASH_PAGE, gather_pages

PAGE, MAX_PAGES = 16, 40                  # three blocks of 16 pages a table
FULL = PAGE * MAX_PAGES - 1

# name: (query heads, column groups, group width, pool dtype, scale, tol)
GEOMETRY = {
    'gpt1-12x64-f32': (12, 12, 64, 'float32', 0.125, 2e-5),
    'granite-32on8x128-bf16': (32, 8, 128, 'bfloat16', 1.0, 2e-2),
}

# name: positions a slot (None: an empty slot); 'shared' is handled apart
RAGGED = {
    'one-row': [0, 3],
    'ends-mid-page': [21, 300],
    'full-table': [FULL, 40],
    'empty-slot': [70, None, 257],
    'shared-prefix': [37, 45],
    'trash-behind-the-table': [5, 400, 17],
}


def _pools(rs, geometry, slots):
    _heads, groups, d, dtype, _scale, _tol = GEOMETRY[geometry]
    shape = (slots * MAX_PAGES + 1, PAGE, groups * d)
    return (jnp.asarray(rs.randn(*shape), dtype),
            jnp.asarray(rs.randn(*shape), dtype))


def _tables(rs, case, positions):
    """Tables under the contract: a live slot names a page of its own
    for every page up to its position, everything else is the trash
    page. ``shared-prefix``: the second slot's first two pages are the
    first slot's."""
    slots = len(positions)
    free = list(1 + rs.permutation(slots * MAX_PAGES))
    tables = np.full((slots, MAX_PAGES), TRASH_PAGE, 'int32')
    for s, pos in enumerate(positions):
        if pos is None:
            continue
        for j in range(pos // PAGE + 1):
            tables[s, j] = free.pop()
    if case == 'shared-prefix':
        tables[1, :2] = tables[0, :2]
    return tables


def _dense(q, keys, values, tables, positions, geometry):
    """gather_pages + dense softmax over what each position has seen,
    float32 at the highest precision (the probabilities rounded to the
    pool's dtype where that is not float32, as ``blocks.attend_rows``
    rounds them)."""
    heads, groups, d, dtype, scale, _tol = GEOMETRY[geometry]
    s = q.shape[0]
    seen = jnp.arange(MAX_PAGES * PAGE)[None] <= positions[:, None]
    k = jnp.where(seen[:, :, None],
                  gather_pages(keys, tables).astype('float32'), 0.0)
    v = jnp.where(seen[:, :, None],
                  gather_pages(values, tables).astype('float32'), 0.0)
    qh = (q.astype('float32') * scale).reshape(s, groups, heads // groups,
                                               d)
    scores = jnp.einsum('sgrd,slgd->sgrl', qh, k.reshape(s, -1, groups, d),
                        precision='highest')
    scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
    att = jax.nn.softmax(scores, -1).astype(dtype).astype('float32')
    return jnp.einsum('sgrl,slgd->sgrd', att, v.reshape(s, -1, groups, d),
                      precision='highest').reshape(s, heads * d)


def _walk(q, keys, values, tables, positions, geometry):
    heads, _groups, _d, _dtype, scale, _tol = GEOMETRY[geometry]
    return jax.jit(lambda *a: flash_paged_decode_attention(
        *a, heads=heads, scale=scale))(q, keys, values, tables, positions)


@pytest.mark.parametrize('case', sorted(RAGGED))
@pytest.mark.parametrize('geometry', sorted(GEOMETRY))
def test_the_walk_equals_gather_and_dense_softmax(geometry, case):
    heads, _groups, d, dtype, _scale, tol = GEOMETRY[geometry]
    rs = np.random.RandomState(len(case))
    positions = RAGGED[case]
    keys, values = _pools(rs, geometry, len(positions))
    tables = _tables(rs, case, positions)
    if case == 'trash-behind-the-table':
        # whatever idle slots wrote there, and worse
        keys = keys.at[TRASH_PAGE].set(jnp.nan)
        values = values.at[TRASH_PAGE, ::2].set(jnp.inf)
    q = jnp.asarray(rs.randn(len(positions), heads * d), dtype)
    live = np.asarray([p is not None for p in positions])
    pos = jnp.asarray([p or 0 for p in positions], 'int32')
    got = np.asarray(_walk(q, keys, values, jnp.asarray(tables), pos,
                           geometry))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    want = np.asarray(_dense(q, keys, values, jnp.asarray(tables), pos,
                             geometry))
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    # an empty slot reads nothing and gets zeros
    assert not got[~live].any()


@pytest.mark.parametrize('geometry', sorted(GEOMETRY))
def test_garbage_beyond_a_position_changes_no_bit(geometry):
    """The rows of a slot's last page past its position, the trash page
    and every page no table names: the walk multiplies none of them."""
    heads, _groups, d, dtype, _scale, _tol = GEOMETRY[geometry]
    rs = np.random.RandomState(7)
    positions = [21, None, 300, FULL - PAGE]
    keys, values = _pools(rs, geometry, len(positions))
    tables = _tables(rs, 'ragged', positions)
    q = jnp.asarray(rs.randn(len(positions), heads * d), dtype)
    pos = jnp.asarray([p or 0 for p in positions], 'int32')
    clean = _walk(q, keys, values, jnp.asarray(tables), pos, geometry)
    named = np.zeros(keys.shape[:2], bool)           # (pages, rows) seen
    for s, p in enumerate(positions):
        if p is None:
            continue
        for j in range(p // PAGE + 1):
            rows = PAGE if j < p // PAGE else p % PAGE + 1
            named[tables[s, j], :rows] = True
    junk = jnp.asarray(np.where(rs.rand(*keys.shape) < 0.5, np.nan, 1e30),
                       dtype)
    dirty_k = jnp.where(named[:, :, None], keys, junk)
    dirty_v = jnp.where(named[:, :, None], values, junk)
    dirty = _walk(q, dirty_k, dirty_v, jnp.asarray(tables), pos, geometry)
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))


def test_which_pool_geometries_mosaic_takes():
    assert paged_walk_fits(16, 768, 'float32')
    assert paged_walk_fits(16, 1024, 'bfloat16')
    assert not paged_walk_fits(8, 1024, 'bfloat16')   # half a bf16 tile
    assert not paged_walk_fits(16, 32, 'float32')     # a toy's 32 columns


# What the walk traces to at GPT-1's and Granite's geometries, as PR 35
# measured it on the chip (`gpt1-chat-steady`, `gpt1-batch-saturated`,
# `granite-h-small-reasoning-saturated`): sha256 of the kernel's jaxpr
# with source locations stripped, on this repo's one installation. A
# change for another geometry must leave these alone; a change meant for
# these is measured on their cells again, and then the pins move.
WALK_JAXPR = {
    'gpt1-12x64-f32':
        '90e87968691ce905c0ea8cf3ba104383078f3e77e3f0fa530c85f20bb50bc88a',
    'granite-32on8x128-bf16':
        '05299415182b60ea8761970f704b6fc9cfe54111ed9c3beac70870dfc15977e8',
}


@pytest.mark.parametrize('geometry', sorted(GEOMETRY))
def test_the_two_pool_geometries_trace_to_what_was_measured(geometry):
    import hashlib
    import re
    heads, groups, d, dtype, scale, _tol = GEOMETRY[geometry]
    slots, pages = 4, 65
    q = jax.ShapeDtypeStruct((slots, heads * d), jnp.float32)
    pool = jax.ShapeDtypeStruct((pages, PAGE, groups * d), jnp.dtype(dtype))
    tables = jax.ShapeDtypeStruct((slots, MAX_PAGES), jnp.int32)
    positions = jax.ShapeDtypeStruct((slots,), jnp.int32)
    text = str(jax.make_jaxpr(lambda *a: flash_paged_decode_attention(
        *a, heads=heads, scale=scale))(q, pool, pool, tables, positions))
    text = re.sub(r'/[\w/.\-]+\.py:\d+', 'FILE', text)
    text = re.sub(r' at 0x[0-9a-f]+', '', text)
    assert 'mxnet_tpu_paged_decode_walk' in text
    assert hashlib.sha256(text.encode()).hexdigest() == WALK_JAXPR[geometry]


# ---------------------------------------------------------------------------
# the latent geometry: one pool, values = the leading columns of the key
# page, every head on the one shared row (xing4_0's cache, published widths)
# ---------------------------------------------------------------------------

L_HEADS, L_WIDTH, L_VALUES, L_USED = 32, 640, 512, 576
LATENT = dict(RAGGED, **{'two-slots-on-the-same-pages': [37, 37]})


def _latent_dense(q, pool, tables, positions):
    """gather_pages + dense softmax, float32 at the highest precision:
    every head scores the whole row and reads its first L_VALUES
    columns."""
    s = q.shape[0]
    seen = jnp.arange(MAX_PAGES * PAGE)[None] <= positions[:, None]
    rows = jnp.where(seen[:, :, None],
                     gather_pages(pool, tables).astype('float32'), 0.0)
    qh = q.astype('float32').reshape(s, L_HEADS, L_WIDTH)
    scores = jnp.einsum('shw,slw->shl', qh, rows, precision='highest')
    scores = jnp.where(seen[:, None, :], scores, -jnp.inf)
    att = jax.nn.softmax(scores, -1).astype('bfloat16').astype('float32')
    return jnp.einsum('shl,slc->shc', att, rows[..., :L_VALUES],
                      precision='highest').reshape(s, L_HEADS * L_VALUES)


def _latent_walk(q, pool, tables, positions):
    return jax.jit(lambda *a: flash_paged_decode_attention(
        a[0], a[1], None, a[2], a[3], heads=L_HEADS, scale=1.0,
        value_cols=L_VALUES))(q, pool, tables, positions)


def _latent_inputs(rs, case, positions):
    slots = len(positions)
    pool = rs.randn(slots * MAX_PAGES + 1, PAGE, L_WIDTH)
    pool[..., L_USED:] = 0.0                   # the pad columns
    tables = _tables(rs, case, positions)
    if case == 'two-slots-on-the-same-pages':
        tables[1] = tables[0]
    q = rs.randn(slots, L_HEADS, L_WIDTH) * 0.2
    q[..., L_USED:] = 0.0
    return (jnp.asarray(q.reshape(slots, -1), 'bfloat16'),
            jnp.asarray(pool, 'bfloat16'), tables)


@pytest.mark.parametrize('case', sorted(LATENT))
def test_the_latent_walk_equals_gather_and_dense_softmax(case):
    rs = np.random.RandomState(len(case))
    positions = LATENT[case]
    q, pool, tables = _latent_inputs(rs, case, positions)
    if case == 'trash-behind-the-table':
        pool = pool.at[TRASH_PAGE].set(jnp.nan)
        # what the query's zero columns meet may be anything finite
        pool = pool.at[1:, :, L_USED:].set(1e30)
    live = np.asarray([p is not None for p in positions])
    pos = jnp.asarray([p or 0 for p in positions], 'int32')
    got = np.asarray(_latent_walk(q, pool, jnp.asarray(tables), pos))
    assert got.shape == (len(positions), L_HEADS * L_VALUES)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    want = np.asarray(_latent_dense(q, pool, jnp.asarray(tables), pos))
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2, atol=2e-2)
    assert not got[~live].any()
    if case == 'two-slots-on-the-same-pages':
        # the same pages, the same position, another query: each slot
        # its own answer from the one copy
        assert not np.array_equal(got[0], got[1])


def test_garbage_beyond_a_latent_position_changes_no_bit():
    rs = np.random.RandomState(11)
    positions = [21, None, 300, FULL - PAGE]
    q, pool, tables = _latent_inputs(rs, 'ragged', positions)
    pos = jnp.asarray([p or 0 for p in positions], 'int32')
    clean = _latent_walk(q, pool, jnp.asarray(tables), pos)
    named = np.zeros(pool.shape[:2], bool)
    for s, p in enumerate(positions):
        if p is None:
            continue
        for j in range(p // PAGE + 1):
            rows = PAGE if j < p // PAGE else p % PAGE + 1
            named[tables[s, j], :rows] = True
    junk = jnp.asarray(np.where(rs.rand(*pool.shape) < 0.5, np.nan, 1e30),
                       'bfloat16')
    dirty = _latent_walk(q, jnp.where(named[:, :, None], pool, junk),
                         jnp.asarray(tables), pos)
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))


def test_the_latent_geometry_is_one_mosaic_takes_padded():
    assert paged_walk_fits(16, L_WIDTH, 'bfloat16')
    assert not paged_walk_fits(16, L_USED, 'bfloat16')   # 4.5 x 128 lanes
    with pytest.raises(ValueError):
        flash_paged_decode_attention(
            jnp.zeros((1, L_WIDTH)), jnp.zeros((2, PAGE, L_WIDTH)), None,
            jnp.zeros((1, 2), 'int32'), jnp.zeros((1,), 'int32'), heads=1)
