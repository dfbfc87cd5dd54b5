"""Block/paged KV cache: a fixed page pool + per-sequence page tables.

PR 6's slot cache preallocates ``slots × max_len`` KV rows per layer —
every admitted sequence reserves its worst-case history whether it
generates 4 tokens or 200, so HBM caps concurrent users long before
compute saturates (the decode memory wall). The paged layout breaks
the reservation into fixed-size **pages** of ``page_size`` rows:

  * the device holds ONE pool per cache entry,
    ``(pages, page_size, *row_shape)``, donated through the step
    program exactly like the slot cache was;
  * each sequence owns a **page table** — a fixed-shape ``int32``
    ``(max_pages,)`` vector of pool page indices — carried into the
    one compiled decode-step program as a plain array argument. The
    program's only cache ops are a gather of the table entries (the
    per-slot K/V view) and one scatter of the step's new rows to
    ``(table[pos // page_size], pos % page_size)`` — never an O(pool)
    copy. Both promise their indices in bounds: the contract on
    tables is in :func:`gather_pages`;
  * **allocation, freeing, refcounting, prefix sharing, and
    copy-on-write decisions all happen host-side** in the engine
    scheduler (:class:`PageAllocator`, :class:`PrefixCache`). The
    compiled program never sees the free list — page churn costs zero
    retraces.

Page 0 is the reserved **trash page**: unused table entries point at
it, and padded prefill writes land in it harmlessly. Reads of trash
rows are masked to exactly 0.0 attention weight (the same additive
``-1e9`` / ``-inf`` argument ``model.py`` makes for padded prefill),
so garbage in page 0 never changes a real sequence's reduction tree —
paged token streams stay bit-identical to the slot cache and to the
uncached whole-sequence reference.

**Prefix sharing**: full pages of a prompt are registered under a
chain key (parent key + the page's token tuple — an exact-match trie,
no hash collisions), and the partial tail page is registered under
the same scheme. A later prompt whose tokens walk the same chain
references those pages read-only (refcount++) instead of re-running
prefill over them. **Copy-on-write**: the first write into a page
whose refcount > 1 (a shared partial tail, or the owner itself once
its tail is registered) copies the page to a fresh one via the tiny
compiled ``copy_page`` program and repoints only that sequence's
table.

Shape/dtype math and the allocator are importable without jax
(engine-testable with fake programs); the device helpers import jax
lazily — the cache.py discipline.
"""
from __future__ import annotations

import heapq

import numpy as onp

__all__ = ['PagedCacheSpec', 'PageAllocator', 'PrefixCache',
           'TRASH_PAGE', 'init_pool', 'pool_avals', 'pool_bytes',
           'gather_pages', 'write_prefill_pages', 'copy_page', 'pages_for',
           'scatter_rows', 'scatter_pages', 'ring_key_positions',
           'window_table_pages']

# pool page index 0 is never allocated: unused page-table entries and
# padded prefill writes target it (reads of it are mask-zeroed)
TRASH_PAGE = 0


def pages_for(n_tokens, page_size):
    """Pages needed to hold ``n_tokens`` KV rows (ceil)."""
    return -(-int(n_tokens) // int(page_size))


def window_table_pages(window, page_size, max_len):
    """Table width of a sliding-window layer: the pages a window of
    ``window`` tokens can straddle, ``ceil(window / page) + 1``, and
    never more than a full layer's ``ceil(max_len / page)``."""
    return min(pages_for(window, page_size) + 1,
               pages_for(max_len, page_size))


class PagedCacheSpec:
    """Metadata for one paged cache: ``{name: (row_shape, dtype)}`` —
    the pool array for ``pages`` pages of ``page_size`` rows each is
    ``(pages, page_size) + row_shape``.

    ``row_shape`` is the per-token shape (``(units,)`` for a
    transformer K or V entry); ``max_pages`` is the per-sequence page
    table length, ``ceil(max_len / page_size)``.

    Two kinds of layer: the entries named in ``window_entries`` belong
    to sliding-window layers. They live in pools and tables of their
    own, sized by the window: a sequence's table for them is a ring of
    ``window_pages`` columns (logical page ``p`` sits in column
    ``p % window_pages``), and the page that falls behind the window
    goes back to their allocator. A spec without window entries
    (``window_pages == 0``) is the one-kind case: one pool size, one
    table, nothing else changes.
    """

    __slots__ = ('entries', 'page_size', 'max_pages', 'window',
                 'window_pages', 'window_entries')

    def __init__(self, entries, page_size, max_len, window=None,
                 window_entries=()):
        self.page_size = int(page_size)
        if self.page_size < 1 or (self.page_size
                                  & (self.page_size - 1)):
            raise ValueError('page_size must be a positive power of '
                             'two, got %d' % self.page_size)
        self.max_pages = pages_for(int(max_len), self.page_size)
        self.entries = {str(k): (tuple(int(d) for d in shape), str(dt))
                        for k, (shape, dt) in dict(entries).items()}
        self.window_entries = frozenset(str(k) for k in window_entries)
        if self.window_entries - set(self.entries):
            raise ValueError('window entries %r are no cache entries'
                             % sorted(self.window_entries
                                      - set(self.entries)))
        self.window = int(window) if self.window_entries else 0
        self.window_pages = window_table_pages(
            self.window, self.page_size, max_len) \
            if self.window_entries else 0

    def items(self):
        return self.entries.items()

    def pages_of(self, name, pages, window_pool):
        """Pool size of one entry: ``window_pool`` for a window
        layer's, ``pages`` for a full layer's."""
        return window_pool if name in self.window_entries else pages

    def full_shape(self, name, pages):
        shape, _ = self.entries[name]
        return (int(pages), self.page_size) + shape

    def to_json(self):
        out = {'page_size': self.page_size,
               'max_pages': self.max_pages,
               'entries': {k: [list(s), dt]
                           for k, (s, dt) in self.entries.items()}}
        if self.window_entries:
            out['window'] = self.window
            out['window_entries'] = sorted(self.window_entries)
        return out

    @classmethod
    def from_json(cls, obj):
        entries = {k: (tuple(s), dt)
                   for k, (s, dt) in obj['entries'].items()}
        return cls(entries, obj['page_size'],
                   obj['max_pages'] * obj['page_size'],
                   window=obj.get('window'),
                   window_entries=obj.get('window_entries', ()))

    def __repr__(self):
        return ('PagedCacheSpec(page_size=%d, max_pages=%d, %r)'
                % (self.page_size, self.max_pages, self.entries))


def pool_bytes(spec, pages, window_pool=0):
    """Static pool footprint in bytes for ``pages`` pages — the REAL
    device residency of the paged cache (the slot cache's
    ``slots × max_len`` figure this replaces reserved worst case per
    sequence whether it was used or not). Window layers' entries
    count ``window_pool`` pages each."""
    total = 0
    for name, (shape, dt) in spec.items():
        n = int(spec.pages_of(name, pages, window_pool)) * spec.page_size
        for d in shape:
            n *= d
        total += n * _itemsize(dt)
    return total


def _itemsize(dt):
    if dt == 'bfloat16':         # numpy alone does not know the name
        return 2
    return onp.dtype(dt).itemsize


def init_pool(spec, pages, window_pool=0):
    """Preallocated zeros pool pytree ``{name: (pages, page_size,
    *row_shape)}`` — zeros so stale rows stay finite under the
    attention mask (cache.py's argument)."""
    import jax.numpy as jnp
    return {name: jnp.zeros(spec.full_shape(
                name, spec.pages_of(name, pages, window_pool)), dt)
            for name, (_, dt) in spec.items()}


def pool_avals(spec, pages, window_pool=0):
    """ShapeDtypeStructs for AOT lowering (freeze.py idiom)."""
    import jax
    return {name: jax.ShapeDtypeStruct(spec.full_shape(
                name, spec.pages_of(name, pages, window_pool)), dt)
            for name, (_, dt) in spec.items()}


# ---------------------------------------------------------------------------
# device-side pool ops (used inside the compiled programs)
# ---------------------------------------------------------------------------


def gather_pages(pool_arr, tables):
    """Per-slot K/V view through the page tables: ``pool_arr``
    (pages, page_size, *row), ``tables`` (slots, max_pages) int32 ->
    (slots, max_pages * page_size, *row).

    The contract on tables, for every pool operation of a compiled
    program (this gather, :func:`scatter_rows`, :func:`scatter_pages`):
    an entry names a page of the pool, and an unused entry holds
    ``TRASH_PAGE``. The engine makes it true in two places: a
    sequence's table starts ``TRASH_PAGE``-filled and afterwards takes
    only pages its ``PageAllocator`` handed out
    (``engine._admit_paged``), and the step's tables start from zeros,
    which is the trash page, and copy live sequences' tables in
    (``engine._paged_step``, ``_spec_step``). So the indices are
    promised in bounds: nothing compares them with the pool's size,
    and nothing lays a fill for out-of-range pages over the view
    (``jnp.take``'s default did: a select over the whole view, 14.6 ms
    of GPT-1's 52 ms step on a v5e; PERF.md section 6, PR 31).

    One XLA gather that writes slots x max_pages x page_size rows to
    HBM, whatever the sequences' real lengths, for the attention to
    read back: the same traffic the slot cache's per-step view cost,
    independent of pool size (the HLO-DECODE-PAGED lint asserts no
    O(pool) materializing copy appears instead). Only attention that
    walks the table itself would read the live rows alone."""
    import jax
    with jax.named_scope('kv_gather'):
        g = pool_arr.at[tables].get(mode='promise_in_bounds')
        s, p, ps = g.shape[:3]                   # (S, P, ps, *row)
        return g.reshape((s, p * ps) + g.shape[3:])


def write_prefill_pages(pool_arr, rows, page_ids):
    """The prefill landing: ``rows`` (npages * page_size, *row) —
    the computed prompt K/V padded to whole pages — scattered page by
    page to the ``page_ids`` (npages,) the host allocated (trailing
    all-padding pages point at the trash page). O(prompt), one
    dynamic_update_slice per page."""
    import jax.numpy as jnp
    from jax import lax
    npages = page_ids.shape[0]
    ps = rows.shape[0] // npages
    for j in range(npages):
        blk = rows[j * ps:(j + 1) * ps]
        start = (jnp.asarray(page_ids[j], 'int32'),
                 jnp.asarray(0, 'int32')) + tuple(
                     jnp.asarray(0, 'int32')
                     for _ in range(pool_arr.ndim - 2))
        pool_arr = lax.dynamic_update_slice(
            pool_arr, blk[None].astype(pool_arr.dtype), start)
    return pool_arr


def scatter_rows(pool_arr, rows, page_ids, offsets):
    """The KV append as one scatter, under :func:`gather_pages`'
    contract: ``rows`` (*batch, *row) to ``(page_ids[b], offsets[b])``,
    both (*batch,) int32 with ``offsets < page_size``. ``batch`` is
    (slots,) for the decode step and (slots, C) for the speculative
    verify's chunk. Live slots never share a (page, offset); free
    slots all target the trash page, where whichever write wins is
    masked anyway."""
    return pool_arr.at[page_ids, offsets].set(
        rows.astype(pool_arr.dtype), mode='promise_in_bounds')


def scatter_pages(pool_arr, rows, page_ids):
    """The prefill landing as one scatter: ``rows`` (npages *
    page_size, *row) to the whole pages ``page_ids`` (npages,). Pages
    the host does not keep (padding, or a window layer's pages behind
    the window) point at the trash page."""
    npages = page_ids.shape[0]
    blocks = rows.reshape((npages, rows.shape[0] // npages)
                          + rows.shape[1:])
    return pool_arr.at[page_ids].set(blocks.astype(pool_arr.dtype),
                                     mode='promise_in_bounds')


def ring_key_positions(positions, window_pages, page_size):
    """Absolute position of every row a window layer's table gathers.

    ``positions`` (slots,) is each slot's newest position. Column
    ``c`` of its ring holds the newest logical page ``p <= positions
    // page_size`` with ``p % window_pages == c``; row ``o`` of it is
    position ``p * page_size + o``. Returns (slots, window_pages *
    page_size) int32, negative where the column has held no page yet.
    """
    import jax.numpy as jnp
    top = (positions // page_size)[:, None]              # (S, 1)
    col = jnp.arange(window_pages)[None, :]              # (1, W)
    page = top - (top - col) % window_pages              # (S, W)
    pos = page[:, :, None] * page_size \
        + jnp.arange(page_size)[None, None, :]
    return pos.reshape(positions.shape[0], -1).astype('int32')


def copy_page(pool_arr, src, dst):
    """Copy one page within the pool (the COW primitive): O(page),
    one dynamic slice + one dynamic update slice."""
    import jax.numpy as jnp
    from jax import lax
    zeros = tuple(jnp.asarray(0, 'int32')
                  for _ in range(pool_arr.ndim - 2))
    blk = lax.dynamic_slice(
        pool_arr, (jnp.asarray(src, 'int32'),
                   jnp.asarray(0, 'int32')) + zeros,
        (1,) + pool_arr.shape[1:])
    return lax.dynamic_update_slice(
        pool_arr, blk, (jnp.asarray(dst, 'int32'),
                        jnp.asarray(0, 'int32')) + zeros)


# ---------------------------------------------------------------------------
# host-side allocation (engine scheduler state; numpy/stdlib only)
# ---------------------------------------------------------------------------


class PageAllocator:
    """Free-list + refcounts over the pool's page indices.

    Page ``TRASH_PAGE`` (0) is reserved. Every allocated page starts
    at refcount 1 (the allocating sequence's hold); prefix-cache
    registration and later sharers take additional holds via
    :meth:`ref`. ``release`` drops a hold and returns the page to the
    free list at zero. Pure host math — no locks (the engine calls it
    under its own scheduler lock) and no jax.
    """

    def __init__(self, pages):
        self.pages = int(pages)
        if self.pages < 2:
            raise ValueError('pool needs >= 2 pages (page 0 is the '
                             'reserved trash page), got %d'
                             % self.pages)
        self.reset()

    def reset(self):
        """Forget everything (the engine rebuilt the device pool —
        every page's contents are garbage now)."""
        # LIFO free list (pop from the end): O(1) per page on the
        # scheduler hot path, and recently-freed pages recycle first
        self._free = list(range(self.pages - 1, 0, -1))
        self._ref = {}

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def used_pages(self):
        return self.pages - 1 - len(self._free)

    def occupancy_pct(self):
        usable = self.pages - 1
        return 100.0 * self.used_pages / usable if usable else 0.0

    def can_alloc(self, n):
        return len(self._free) >= int(n)

    def alloc(self, n):
        """``n`` fresh pages at refcount 1, or None when the pool
        cannot satisfy the request (the caller evicts or rejects
        typed — never a partial grant)."""
        n = int(n)
        if len(self._free) < n:
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        return out

    def ref(self, page):
        """Take one more hold on an allocated page (prefix sharing)."""
        if page == TRASH_PAGE:
            return page
        if page not in self._ref:
            raise ValueError('ref of unallocated page %d' % page)
        self._ref[page] += 1
        return page

    def refcount(self, page):
        return self._ref.get(page, 0)

    def release(self, page):
        """Drop one hold; at zero the page returns to the free list."""
        if page == TRASH_PAGE:
            return
        cnt = self._ref.get(page)
        if cnt is None:
            raise ValueError('release of unallocated page %d' % page)
        if cnt <= 1:
            del self._ref[page]
            self._free.append(page)
        else:
            self._ref[page] = cnt - 1

    def stats(self):
        return {'pages_total': self.pages - 1,
                'pages_free': self.free_pages,
                'pages_used': self.used_pages,
                'occupancy_pct': round(self.occupancy_pct(), 2)}


class _PrefixNode:
    __slots__ = ('id', 'key', 'page', 'parent', 'children', 'touch')

    def __init__(self, ident, key, page, parent):
        self.id = ident               # serial of this registration
        self.key = key                # (parent's id or root, tokens)
        self.page = page
        self.parent = parent          # parent node or None
        self.children = 0
        self.touch = 0                # serial of its last touch


class PrefixCache:
    """Exact-match trie of prompt pages → pool page indices.

    Keys are ``(parent, tokens_tuple)``, ``parent`` being the serial
    number of the parent's registration (never used twice) or the
    namespace's root: the chain itself is the key, so two different
    prefixes can never collide the way a rolling hash could, and a key
    hashes in the time of one page, not of the chain behind it. Full
    pages chain with ``len(tokens) == page_size``; the prompt's
    partial tail page registers with its shorter token tuple (shared
    only on an exact remaining-token match — a divergence INSIDE a
    page can therefore never alias, and a sharer writing past the
    shared rows copy-on-writes first).

    Each registered node holds one allocator ref on its page, so a
    retired owner's pages survive for future hits until
    :meth:`evict_lru` reclaims them under pool pressure (leaf-first,
    least-recently-used — a parent page is never freed while a child
    still chains through it).
    """

    def __init__(self, page_size, allocator):
        self.page_size = int(page_size)
        self._alloc = allocator
        self._nodes = {}        # key -> node
        self._by_page = {}      # page id -> node (pages are
        self._serial = 0        # registered under at most one node)
        # (touch, serial, node) for every node as it was when last it
        # was touched as a leaf or lost its last child. The smallest
        # entry that still describes its node is the least recently
        # used leaf: an eviction pops it and walks nothing
        self._leaves = []
        self.evictions = 0      # hit/token counters live in the
                                # engine's _counts, not here

    def __len__(self):
        return len(self._nodes)

    def clear(self):
        """Drop every registration WITHOUT releasing pages — used when
        the allocator itself was reset (pool rebuilt)."""
        self._nodes = {}
        self._by_page = {}
        self._leaves = []

    def _next(self):
        self._serial += 1
        return self._serial

    def _touch(self, node):
        node.touch = self._next()
        if not node.children:
            self._push_leaf(node)

    def _push_leaf(self, node):
        if len(self._leaves) > 4 * len(self._nodes) + 64:
            # mostly entries that a later touch or a child overtook
            self._leaves = [(n.touch, n.id, n)
                            for n in self._nodes.values()
                            if not n.children and n is not node]
            heapq.heapify(self._leaves)
        heapq.heappush(self._leaves, (node.touch, self._next(), node))

    def _drop(self, node):
        """Forget one leaf and give back the registry's hold on its
        page; its parent may be a leaf now."""
        del self._nodes[node.key]
        del self._by_page[node.page]
        parent = node.parent
        if parent is not None:
            parent.children -= 1
            if not parent.children:
                self._push_leaf(parent)
        self._alloc.release(node.page)

    def _chunks(self, prompt):
        ps = self.page_size
        full = len(prompt) // ps
        out = [tuple(prompt[i * ps:(i + 1) * ps])
               for i in range(full)]
        tail = tuple(prompt[full * ps:])
        return out, tail

    @staticmethod
    def _root(namespace):
        """Root parent key for one namespace. ``None`` is the base
        model's; anything else — the engine passes the adapter id —
        roots a disjoint trie, so a warm prefix hit can NEVER splice
        base-model KV rows into an adapter sequence or cross two
        adapters: their K/V for the same tokens differ."""
        return None if namespace is None else ('ns', str(namespace))

    def register(self, prompt, page_ids, namespace=None):
        """Record ``prompt``'s pages (full chain + partial tail) for
        future sharers; takes one allocator ref per NEWLY registered
        page. ``page_ids[i]`` holds prompt positions
        ``[i*ps, (i+1)*ps)``. ``namespace`` isolates the chain (the
        engine namespaces by adapter id)."""
        chunks, tail = self._chunks(prompt)
        parent, above = None, self._root(namespace)
        for i, chunk in enumerate(chunks + ([tail] if tail else [])):
            key = (above, chunk)
            node = self._nodes.get(key)
            if node is None:
                page = page_ids[i]
                if page == TRASH_PAGE:
                    break              # prompt outran the page list
                self._alloc.ref(page)
                node = _PrefixNode(self._next(), key, page, parent)
                self._nodes[key] = node
                self._by_page[page] = node
                if parent is not None:
                    parent.children += 1
            self._touch(node)
            parent, above = node, node.id

    def lookup(self, prompt, namespace=None):
        """Longest registered chain covering ``prompt``'s head IN
        ``namespace``: returns ``(page_ids, tokens_covered)`` WITHOUT
        taking refs (the engine refs the pages it actually uses). Full
        pages chain first; a partial tail matches only when the
        remaining prompt tokens equal a registered tail exactly."""
        chunks, tail = self._chunks(prompt)
        pages = []
        above = self._root(namespace)
        covered = 0
        for chunk in chunks + ([tail] if tail else []):
            node = self._nodes.get((above, chunk))
            if node is None:
                break
            self._touch(node)
            pages.append(node.page)
            covered += len(chunk)
            above = node.id
        return pages, covered

    def release_leaf(self, page):
        """Drop the LEAF registration holding ``page`` — the
        copy-on-write fast path: when a page's only co-holder is the
        registry itself (refcount 2: owner + registration), stealing
        the registration back makes the owner's write private WITHOUT
        a page copy. Only leaves are stealable (a mid-chain page must
        stay registered or its descendants' chains dangle); partial
        tail pages — the common trigger, every non-aligned prompt's
        own generation — are always leaves. Returns True when a leaf
        registration was dropped. O(1) via the page->node index (this
        runs per page-boundary write on the scheduler hot path)."""
        node = self._by_page.get(page)
        if node is None or node.children:
            return False
        self._drop(node)
        return True

    def evict_lru(self, want_pages=1):
        """Drop least-recently-used LEAF registrations until
        ``want_pages`` allocator pages could be satisfied (or nothing
        evictable remains). Returns the freed page ids (pages whose
        only remaining hold was the registry's)."""
        freed = []
        while not self._alloc.can_alloc(want_pages) and self._leaves:
            touch, _serial, node = heapq.heappop(self._leaves)
            if self._nodes.get(node.key) is not node or node.children \
                    or node.touch != touch:
                continue               # an entry its node has outlived
            before = self._alloc.free_pages
            self._drop(node)
            if self._alloc.free_pages > before:
                freed.append(node.page)
            self.evictions += 1
        return freed
