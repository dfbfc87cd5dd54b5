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
from mxnet_tpu.ops.pallas.attention import (_RUN_PAGES, walk_block_pages,
                                            walk_copy_runs)
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
    seen = jnp.arange(tables.shape[1] * PAGE)[None] <= positions[:, None]
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


# What the walk traces to at GPT-1's and Granite's geometries, as PR 37
# measured it on the chip (copies by run; `gpt1-chat-steady`,
# `granite-h-small-reasoning-saturated`): sha256 of the kernel's jaxpr
# with source locations stripped, on this repo's one installation. PR 36
# pinned PR 35's to show it left these two geometries alone. A change
# for another geometry must leave these alone; a change meant for these
# is measured on their cells again, and then the pins move.
WALK_JAXPR = {
    'gpt1-12x64-f32':
        '5b1d3b9deeb412c3f7df2bc120e22fcf7e06dcf38c177754ff55818b0a716af6',
    'granite-32on8x128-bf16':
        'cfc1d5a65404e7927e74b2b4f9f38a7af7b1a8a83f8c77fac107fa71802e17d9',
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
    seen = jnp.arange(tables.shape[1] * PAGE)[None] <= positions[:, None]
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


# ---------------------------------------------------------------------------
# copies by run (PR 37): a chunk of consecutive pages is one copy a pool;
# where the pages lie changes no bit of the result
# ---------------------------------------------------------------------------

LAYOUTS = ('one-run', 'broken-mid-chunk-and-at-an-edge', 'descending',
           'shared-prefix-then-scattered', 'empty-slots-between',
           'last-block-under-a-chunk')
LATENT_GEOMETRY = 'xing4-32x640-bf16'


def _block_pages(geometry):
    if geometry == LATENT_GEOMETRY:
        return walk_block_pages(1, PAGE, L_WIDTH, 'bfloat16')
    _heads, groups, d, dtype, _scale, _tol = GEOMETRY[geometry]
    return walk_block_pages(2, PAGE, groups * d, dtype)


def _lay(layout, b):
    """(positions, tables) of a layout of pages over blocks of ``b``
    pages: page ids from 1, the trash page everywhere else; an empty
    slot's position is None."""
    def run(first, n):
        return list(range(first, first + n))

    r = _RUN_PAGES
    rows = {
        # whole blocks and a tail of a whole chunk and 3 pages; one
        # block; less than a chunk
        'one-run': [run(1, 2 * b + r + 3), run(400, b), run(600, 5)],
        # a break inside chunk 0, then runs; a break at a chunk's edge
        'broken-mid-chunk-and-at-an-edge': [
            run(1, 3) + run(50, b + 2 * r - 3), run(400, r) + run(500, 2 * r)],
        # what a freed sequence's pages give back
        'descending': [run(1, b + 5)[::-1], run(300, 2 * r)[::-1]],
        'shared-prefix-then-scattered': [
            run(10, b + r + 4) + [301, 299, 305, 290, 291],
            run(10, b + r + 4) + [400, 300, 403]],
        'empty-slots-between': [run(1, b + 1), [], [], run(200, 2 * r), []],
        # a last block of fewer pages than a chunk
        'last-block-under-a-chunk': [run(1, b + 3), run(200, 2),
                                     run(300, r - 1)],
    }[layout]
    tables = np.full((len(rows), max(MAX_PAGES, max(map(len, rows)))),
                     TRASH_PAGE, 'int32')
    for s, row in enumerate(rows):
        tables[s, :len(row)] = row
    # somewhere in the last page, its end too
    positions = [len(row) * PAGE - 1 - (5 * s) % PAGE if row else None
                 for s, row in enumerate(rows)]
    return positions, tables


def _flags(tables, positions, block_pages):
    """The scalars ``_paged_walk`` hands the kernel: bit ``c`` of block
    ``j``."""
    run, _copies = walk_copy_runs(np, tables, np.asarray(positions), PAGE,
                                  block_pages, TRASH_PAGE)
    per_block = block_pages // min(_RUN_PAGES, block_pages)
    blocks = -(-tables.shape[1] // block_pages)
    run = np.pad(run, ((0, 0), (0, blocks * per_block - run.shape[1])))
    return (run.reshape(len(run), blocks, per_block)
            << np.arange(per_block)).sum(-1)


def _copies_by_hand(tables, positions, block_pages):
    """The copies a pool that the kernel's loops issue, a slot: the
    flags read the way ``block_copies`` reads them."""
    runs = _flags(tables, positions, block_pages)
    chunk = min(_RUN_PAGES, block_pages)
    out = []
    for s, pos in enumerate(positions):
        n = 0
        if tables[s, 0] != TRASH_PAGE:
            for j in range(pos // (block_pages * PAGE) + 1):
                count = min(block_pages, pos // PAGE + 1 - j * block_pages)
                for c in range(-(-count // chunk)):
                    n += 1 if runs[s, j] >> c & 1 \
                        else min(chunk, count - c * chunk)
        out.append(n)
    return out


@pytest.mark.parametrize('layout', LAYOUTS)
@pytest.mark.parametrize('geometry', sorted(GEOMETRY) + [LATENT_GEOMETRY])
def test_where_the_pages_lie_changes_no_bit(geometry, layout):
    """The walk over tables with runs equals, bit for bit, the same walk
    over a pool whose pages were permuted so that no two of a table are
    consecutive (the same rows in the same order, every copy a single
    page: the parent's schedule), and gather + dense softmax to
    rounding."""
    latent = geometry == LATENT_GEOMETRY
    block_pages = _block_pages(geometry)
    positions, tables = _lay(layout, block_pages)
    live = np.asarray([p is not None for p in positions])
    pos = np.asarray([p or 0 for p in positions], 'int32')
    rs = np.random.RandomState(len(layout))
    pages = int(tables.max()) + 1
    if latent:
        tol, width = 2e-2, L_WIDTH
        pool = rs.randn(pages, PAGE, width)
        pool[..., L_USED:] = 0.0
        pools = (jnp.asarray(pool, 'bfloat16'),)
        q = rs.randn(len(positions), L_HEADS, width) * 0.2
        q[..., L_USED:] = 0.0
        q = jnp.asarray(q.reshape(len(positions), -1), 'bfloat16')

        def walk(pools, tables):
            return _latent_walk(q, pools[0], jnp.asarray(tables), pos)
        want = _latent_dense(q, pools[0], jnp.asarray(tables),
                             jnp.asarray(pos))
    else:
        heads, groups, d, dtype, _scale, tol = GEOMETRY[geometry]
        pools = tuple(jnp.asarray(rs.randn(pages, PAGE, groups * d), dtype)
                      for _ in range(2))
        q = jnp.asarray(rs.randn(len(positions), heads * d), dtype)

        def walk(pools, tables):
            return _walk(q, *pools, jnp.asarray(tables), jnp.asarray(pos),
                         geometry)
        want = _dense(q, *pools, jnp.asarray(tables), jnp.asarray(pos),
                      geometry)
    # the layout has what its name says: runs where there should be some
    run, copies = walk_copy_runs(np, tables, pos, PAGE, block_pages,
                                 TRASH_PAGE)
    walked = np.where(live, pos // PAGE + 1, 0)
    assert list(copies) == _copies_by_hand(tables, pos, block_pages)
    assert (copies <= walked).all() and not copies[~live].any()
    if layout == 'descending':
        assert list(copies) == list(walked) and not run.any()
    else:
        assert copies.sum() < walked.sum()
    # page p of the pool goes to 2 p: no table entry follows another
    apart = tuple(jnp.zeros((2 * pages,) + x.shape[1:], x.dtype)
                  .at[::2].set(x) for x in pools)
    assert not walk_copy_runs(np, 2 * tables, pos, PAGE, block_pages,
                              TRASH_PAGE)[0].any()
    got = np.asarray(walk(pools, tables))
    assert np.array_equal(got, np.asarray(walk(apart, 2 * tables)))
    assert np.isfinite(got).all() and not got[~live].any()
    np.testing.assert_allclose(got[live], np.asarray(want)[live],
                               rtol=tol, atol=tol)


def test_the_kernels_flags_are_the_owners_count():
    """``walk_copy_runs`` through ``jax.numpy`` (the kernel's flags) and
    through ``numpy`` (``PageOwner.step_copies``) is one rule."""
    for layout in LAYOUTS:
        for b in (1, 4, 16, 64):
            positions, tables = _lay(layout, b)
            pos = np.asarray([p or 0 for p in positions], 'int32')
            run, copies = walk_copy_runs(np, tables, pos, PAGE, b,
                                         TRASH_PAGE)
            jrun, jcopies = jax.jit(
                lambda t, p, b=b: walk_copy_runs(jnp, t, p, PAGE, b,
                                                 TRASH_PAGE))(tables, pos)
            assert np.array_equal(run, np.asarray(jrun))
            assert np.array_equal(copies, np.asarray(jcopies))
            assert list(copies) == _copies_by_hand(tables, pos, b)
