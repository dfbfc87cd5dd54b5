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
    copy-on-write decisions all happen host-side**, in one place:
    :class:`PageOwner` holds, for every kind of layer the spec has, a
    :class:`PageAllocator`, a :class:`PrefixCache` and every live
    sequence's tables and holds; the engine's scheduler asks it and
    knows no kind. The compiled program never sees the free list —
    page churn costs zero retraces.

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

from ...observability.spans import span as _span

__all__ = ['PagedCacheSpec', 'PageAllocator', 'PrefixCache', 'PageOwner',
           'SeqPages', 'TRASH_PAGE', 'init_pool', 'pool_avals', 'pool_bytes',
           'slot_state_bytes',
           'gather_pages', 'walks_pages', 'write_prefill_pages',
           'copy_page', 'pages_for',
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

    A third kind of entry is no page at all: ``slot_entries``, ``{name:
    (shape, dtype)}``, are recurrent state of fixed size a sequence
    (a state-space layer's carried state and its convolution's last
    inputs). A table for them would be the identity, so they have none:
    the array is ``(slots,) + shape`` in the same donated pytree, row
    ``s`` belongs to slot ``s``, a prefill rewrites its slot's row
    whole and the step updates the rows of the slots that step. No
    allocator hands them out and nothing is registered for them; a
    cache that has them shares no prefix (:class:`PageOwner`): K/V
    pages found by tokens without the state at that boundary would
    decode wrongly.
    """

    __slots__ = ('entries', 'page_size', 'max_pages', 'window',
                 'window_pages', 'window_entries', 'slot_entries',
                 'entries_per_layer')

    def __init__(self, entries, page_size, max_len, window=None,
                 window_entries=(), slot_entries=None,
                 entries_per_layer=2):
        self.page_size = int(page_size)
        # a K and a V entry an attention layer, or one entry of latent
        # rows that are keys and values both
        self.entries_per_layer = int(entries_per_layer)
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
        self.slot_entries = {
            str(k): (tuple(int(d) for d in shape), str(dt))
            for k, (shape, dt) in dict(slot_entries or {}).items()}
        if set(self.slot_entries) & set(self.entries):
            raise ValueError('entries %r are both paged and a slot\'s'
                             % sorted(set(self.slot_entries)
                                      & set(self.entries)))

    def items(self):
        """The paged entries (:attr:`slot_entries` are apart)."""
        return self.entries.items()

    def kinds(self):
        """The kinds of layer this cache has, as ``(name, table
        columns, ring)``: ``full`` first, whose table has a column for
        every page of ``max_len``; ``window`` where there are window
        entries, a ring of ``window_pages`` columns."""
        out = [('full', self.max_pages, False)]
        if self.window_entries:
            out.append(('window', self.window_pages, True))
        return out

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
        if self.slot_entries:
            out['slot_entries'] = {k: [list(s), dt] for k, (s, dt)
                                   in self.slot_entries.items()}
        if self.entries_per_layer != 2:
            out['entries_per_layer'] = self.entries_per_layer
        return out

    @classmethod
    def from_json(cls, obj):
        entries = {k: (tuple(s), dt)
                   for k, (s, dt) in obj['entries'].items()}
        return cls(entries, obj['page_size'],
                   obj['max_pages'] * obj['page_size'],
                   window=obj.get('window'),
                   window_entries=obj.get('window_entries', ()),
                   slot_entries={k: (tuple(s), dt) for k, (s, dt) in
                                 obj.get('slot_entries', {}).items()},
                   entries_per_layer=obj.get('entries_per_layer', 2))

    def __repr__(self):
        return ('PagedCacheSpec(page_size=%d, max_pages=%d, %r)'
                % (self.page_size, self.max_pages, self.entries))


def slot_state_bytes(spec, slots=1):
    """Bytes of ``spec``'s slot entries for ``slots`` sequences (0
    where it has none): what a sequence costs whatever its length."""
    total = 0
    for shape, dt in spec.slot_entries.values():
        total += int(onp.prod(shape, dtype='int64')) * _itemsize(dt)
    return total * int(slots)


def pool_bytes(spec, pages, window_pool=0, slots=0):
    """Static pool footprint in bytes for ``pages`` pages — the REAL
    device residency of the paged cache (the slot cache's
    ``slots × max_len`` figure this replaces reserved worst case per
    sequence whether it was used or not). Window layers' entries
    count ``window_pool`` pages each, slot entries ``slots`` rows."""
    total = slot_state_bytes(spec, slots)
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


def _pool_tree(spec, pages, window_pool, slots, leaf):
    out = {name: leaf(spec.full_shape(
               name, spec.pages_of(name, pages, window_pool)), dt)
           for name, (_, dt) in spec.items()}
    out.update({name: leaf((int(slots),) + shape, dt)
                for name, (shape, dt) in spec.slot_entries.items()})
    return out


def init_pool(spec, pages, window_pool=0, slots=0):
    """Preallocated zeros pool pytree ``{name: (pages, page_size,
    *row_shape)}`` — zeros so stale rows stay finite under the
    attention mask (cache.py's argument) — and, for a spec with slot
    entries, ``{name: (slots, *shape)}`` beside them."""
    import jax.numpy as jnp
    return _pool_tree(spec, pages, window_pool, slots, jnp.zeros)


def pool_avals(spec, pages, window_pool=0, slots=0):
    """ShapeDtypeStructs for AOT lowering (freeze.py idiom)."""
    import jax
    return _pool_tree(spec, pages, window_pool, slots,
                      jax.ShapeDtypeStruct)


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
    ``TRASH_PAGE``. :class:`PageOwner` makes it true in two places: a
    sequence's table starts ``TRASH_PAGE``-filled and afterwards takes
    only pages its kind's ``PageAllocator`` handed out
    (:meth:`PageOwner.open` and what follows it), and the step's
    tables start from zeros, which is the trash page, and copy live
    sequences' tables in (:meth:`PageOwner.tables`). So the indices are
    promised in bounds: nothing compares them with the pool's size,
    and nothing lays a fill for out-of-range pages over the view
    (``jnp.take``'s default did: a select over the whole view, 14.6 ms
    of GPT-1's 52 ms step on a v5e; PERF.md section 6, PR 31).

    One XLA gather that writes slots x max_pages x page_size rows to
    HBM, whatever the sequences' real lengths, for the attention to
    read back: the same traffic the slot cache's per-step view cost,
    independent of pool size (the HLO-DECODE-PAGED lint asserts no
    O(pool) materializing copy appears instead). Only attention that
    walks the table itself reads the live rows alone: the one-token
    step's does on a TPU (:func:`walks_pages`); the verify's chunk,
    the prefills and the ring tables of a window layer gather."""
    import jax
    with jax.named_scope('kv_gather'):
        g = pool_arr.at[tables].get(mode='promise_in_bounds')
        s, p, ps = g.shape[:3]                   # (S, P, ps, *row)
        return g.reshape((s, p * ps) + g.shape[3:])


def walks_pages(pool_shape, dtype):
    """Whether the one-token step's attention over a pool of
    ``pool_shape`` (pages, page_size, width) walks the page table
    inside one kernel (``ops.pallas.flash_paged_decode_attention``: a
    slot's live pages are read from the pool where they lie) or
    gathers a view (:func:`gather_pages`). The walk runs where the
    computation being traced is placed on a TPU and the pool's
    geometry is one Mosaic takes; the CPU rig and the serving CPU
    replay gather. Decided on what the trace can observe, by no knob."""
    from ...ops.pallas import interpret_mode, paged_walk_fits
    return not interpret_mode() and paged_walk_fits(
        pool_shape[1], pool_shape[2], dtype)


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


# ---------------------------------------------------------------------------
# one owner of pages (host side), keyed by kind of layer
# ---------------------------------------------------------------------------


class _Kind:
    """What one kind of layer has on the host: its pools' allocator,
    its prefix registry (None: the owner was built without one) and
    its table geometry."""

    __slots__ = ('name', 'columns', 'ring', 'layers', 'allocator',
                 'prefix')

    def __init__(self, name, columns, ring, layers, allocator, prefix):
        self.name = name
        self.columns = int(columns)
        # attention layers of this kind (a layer has a K and a V entry,
        # or one entry of latent rows: spec.entries_per_layer)
        self.layers = int(layers)
        # a ring keeps logical page p in column p % columns and gives
        # back the page that falls behind; a table that is no ring has
        # a column for every page a sequence can reach
        self.ring = bool(ring)
        self.allocator = allocator
        self.prefix = prefix


class SeqPages:
    """One live sequence's pages, by kind of layer: its table
    (``TRASH_PAGE`` where it holds nothing), the pages it holds a
    reference on and, for a ring, the highest logical page it has
    opened. Made by :meth:`PageOwner.open`; only the owner writes it."""

    __slots__ = ('slot', 'tables', 'held', 'top')

    def __init__(self, slot, kinds):
        self.slot = slot
        self.tables = {k.name: onp.full(k.columns, TRASH_PAGE, 'int32')
                       for k in kinds}
        self.held = {k.name: [] for k in kinds}
        self.top = {k.name: -1 for k in kinds if k.ring}


class PageOwner:
    """Who holds which page when: the one host-side owner of a paged
    cache's pages, for every kind of layer ``spec`` has.

    The scheduler (engine.py) keeps one of these and one
    :class:`SeqPages` a sequence, and knows no kind of layer: every
    method loops over the kinds. A third kind is an entry in
    :meth:`PagedCacheSpec.kinds` and its geometry here.

    Page ids and tables go out in the form the compiled programs take
    them (``PagedDecodeProgram.run_prefill`` / ``run_step`` /
    ``run_verify`` / ``run_copy_page``): bare where the cache has one
    kind of layer, ``{kind: ...}`` where it has more.

    ``lock`` is the scheduler's: every change of an allocator, a
    registry or a record's holds is made under it, so that a reader of
    the figures (:meth:`stats`, :meth:`pool_stats`, under the same
    lock) never sees a pool half reset. ``counts`` is the scheduler's
    counter dict: ``prefix_hits``, ``prefix_tokens_saved``,
    ``page_evictions``, ``cow_copies`` and, with a ring,
    ``window_pages_released`` are booked here and nowhere else
    (``kv_pages_walked``, ``kv_pages_view`` and ``kv_page_copies`` are
    reckoned here, :meth:`step_pages` and :meth:`step_copies`, and
    booked by the scheduler with its step).
    ``event(kind, **fields)`` takes the flight recorder's
    ``page_alloc`` and ``page_evict``. Worker thread only, but for the
    readers and :meth:`drop`.

    Slot entries (recurrent state, :class:`PagedCacheSpec`) are
    accounted for here and allocated nowhere: a sequence that was
    placed holds its slot's rows until it is dropped, :meth:`place`
    names the slot beside the page ids for the prefill that rewrites
    them, the step needs no table for them, and their bytes count in
    :meth:`held_bytes` and in the gauge ``state_bytes_live``. With
    slot entries no prefix is registered or shared, whatever
    ``prefix_cache`` asks for: ``prefix_hits`` stays 0."""

    def __init__(self, spec, pool_pages, lock, prefix_cache, counts,
                 event=None):
        self.page_size = spec.page_size
        self._spec = spec
        prefix_cache = bool(prefix_cache) and not spec.slot_entries
        self._state_bytes = slot_state_bytes(spec)
        self._stateful = set()        # slots whose state rows are live
        self._lock = lock
        self._counts = counts
        self._event = event or (lambda kind, **fields: None)
        self._kinds = []
        window = len(spec.window_entries)
        for name, columns, ring in spec.kinds():
            allocator = PageAllocator(pool_pages[name])
            self._kinds.append(_Kind(
                name, columns, ring,
                (window if ring else len(spec.entries) - window)
                // spec.entries_per_layer,
                allocator,
                PrefixCache(spec.page_size, allocator)
                if prefix_cache else None))
        self._rings = [k for k in self._kinds if k.ring]
        self._registers = bool(prefix_cache)
        # pages of one slot's gathered views, over all attention layers
        self._view_pages = sum(k.layers * k.columns for k in self._kinds)
        # pages of one block of the step's walk, None where the step
        # gathers: off a TPU (:func:`walks_pages`), and every layer of
        # a cache that has a ring (cohere2.py)
        self._walk_block = None
        (width, *more), dtype = next(iter(spec.entries.values()))
        if not self._rings and not more and walks_pages(
                (0, spec.page_size, width), dtype):
            from ...ops.pallas.attention import walk_block_pages
            self._walk_block = walk_block_pages(
                spec.entries_per_layer, spec.page_size, width, dtype)
        for name in ('prefix_hits', 'prefix_tokens_saved',
                     'page_evictions', 'cow_copies', 'kv_pages_walked',
                     'kv_pages_view', 'kv_page_copies'):
            counts.setdefault(name, 0)
        if self._rings:
            counts.setdefault('window_pages_released', 0)

    @staticmethod
    def _out(by_kind):
        """``by_kind`` as the compiled programs take it."""
        if len(by_kind) == 1:
            return next(iter(by_kind.values()))
        return by_kind

    # -- a sequence's life -------------------------------------------------

    def open(self, slot):
        """The record of a sequence that joins ``slot``: tables full of
        the trash page, nothing held."""
        return SeqPages(slot, self._kinds)

    def share_prefix(self, rec, prompt, namespace=None):
        """Look ``prompt`` up in every kind's registry under
        ``namespace`` (the scheduler passes the adapter id: an
        adapter's K/V rows for the same tokens differ from the base's)
        and take what is found into ``rec``. A hit reaches as far as
        EVERY kind still holds the prefix (a ring registers only
        prompts it holds whole, and each registry evicts on its own)
        and always leaves one token to step on: its logits are the
        first generated token. Returns ``(tokens covered, pages
        shared)``, ``(0, 0)`` on a miss."""
        if not self._registers:
            return 0, 0
        ps = self.page_size
        with self._lock:
            hits, covered = {}, len(prompt)
            for k in self._kinds:
                hits[k.name], found = k.prefix.lookup(
                    prompt, namespace=namespace)
                covered = min(covered, found)
            npages = pages_for(covered, ps)
            covered = min(covered, len(prompt) - 1)
            if covered <= 0:
                return 0, 0
            for k in self._kinds:
                ids = hits[k.name][:npages]
                for page in ids:
                    k.allocator.ref(page)
                rec.held[k.name] = list(ids)
                # a registered prefix fits a ring: column = page
                rec.tables[k.name][:len(ids)] = ids
                if k.ring:
                    rec.top[k.name] = len(ids) - 1
            self._counts['prefix_hits'] += 1
            self._counts['prefix_tokens_saved'] += covered
        return covered, npages

    def place(self, rec, n_tokens):
        """Pages for positions ``[0, n_tokens)`` of ``rec`` in every
        kind's pools: a prefill's landing, or an imported sequence's. A
        ring keeps the last ``columns`` pages only: what lies behind
        it is never written. Registered prefixes are evicted, least
        recently used first, under pool pressure. Returns the page ids
        of each logical page (the trash page for those behind a ring)
        and, where the cache has slot entries, ``'slot'``: the row the
        prefill rewrites; or None on exhaustion with nothing left
        held."""
        npages = pages_for(n_tokens, self.page_size)
        out = {}
        for k in self._kinds:
            behind = max(0, npages - k.columns) if k.ring else 0
            ids = self._alloc(k, npages - behind, rec.slot)
            if ids is None:
                with self._lock:
                    self.drop(rec)
                return None
            with self._lock:
                rec.held[k.name] = list(ids)
            table = rec.tables[k.name]
            if k.ring:
                for j, page in enumerate(ids):
                    table[(behind + j) % k.columns] = page
                rec.top[k.name] = npages - 1
            else:
                table[:npages] = ids
            out[k.name] = [TRASH_PAGE] * behind + ids
        if self._state_bytes:
            out['slot'] = rec.slot
            with self._lock:
                self._stateful.add(rec.slot)
        return self._out(out)

    def register(self, prompt, ids, namespace=None):
        """Record a prompt's pages, ``ids`` as :meth:`place` returned
        them, for later sharers. A ring the prompt outran registers
        nothing: its first page is the trash page."""
        if not self._registers:
            return
        if len(self._kinds) == 1:
            ids = {self._kinds[0].name: ids}
        with _span('eng.tick.prefix_register'), self._lock:
            for k in self._kinds:
                k.prefix.register(prompt, ids[k.name],
                                  namespace=namespace)

    def make_writable(self, rec, first_pos, last_pos, copy):
        """Make every page this tick writes, positions
        ``first_pos..last_pos`` of ``rec``, privately writable in every
        kind: allocate at a page boundary, take a registration back
        whose only other holder is the registry, else copy on write.
        ``copy(src, dst)`` runs the device's page copy (ids as the
        program takes them: the trash page onto itself for the kinds
        that do not copy); what it raises goes to the caller. False on
        pool exhaustion (after eviction)."""
        ps = self.page_size
        for page_no in range(int(first_pos) // ps,
                             int(last_pos) // ps + 1):
            for k in self._kinds:
                # :meth:`advance` has emptied the column of a page that
                # this position opens in a ring
                col = page_no % k.columns if k.ring else page_no
                if not self._writable(rec, k, col, copy):
                    return False
        return True

    def _writable(self, rec, k, col, copy):
        table, held = rec.tables[k.name], rec.held[k.name]
        page = int(table[col])
        if page == TRASH_PAGE:
            ids = self._alloc(k, 1, rec.slot)
            if ids is None:
                return False
            table[col] = ids[0]
            with self._lock:
                held.append(ids[0])
            return True
        with self._lock:
            shared = k.allocator.refcount(page) > 1
            if shared and k.prefix is not None \
                    and k.allocator.refcount(page) == 2:
                # the only co-holder is the prefix registry: steal the
                # registration back instead of copying — the write is
                # private, no extra page burned (real sharers keep the
                # full copy-on-write below)
                if k.prefix.release_leaf(page):
                    shared = k.allocator.refcount(page) > 1
        if not shared:
            return True
        # copy-on-write: the first divergent write into a shared page
        # lands in this sequence's private copy
        ids = self._alloc(k, 1, rec.slot)
        if ids is None:
            return False
        copy(self._out({o.name: page if o is k else TRASH_PAGE
                        for o in self._kinds}),
             self._out({o.name: ids[0] if o is k else TRASH_PAGE
                        for o in self._kinds}))
        with self._lock:
            k.allocator.release(page)
            held.remove(page)
            held.append(ids[0])
            self._counts['cow_copies'] += 1
        table[col] = ids[0]
        return True

    def advance(self, live):
        """Before the step writes: ``live`` is ``(record, next
        position)`` of every sequence that steps. A sequence whose
        position opens a logical page its ring has not held yet gives
        back the page that column held: by then it lies wholly behind
        the window (the ring is ``ceil(window / page) + 1`` columns).
        The registry's own hold, if the page was a shared prefix, keeps
        it for later hits."""
        if not self._rings:
            return
        ps = self.page_size
        with _span('eng.tick.release_window'):
            released = 0
            for rec, pos in live:
                top = int(pos) // ps
                for k in self._rings:
                    if top <= rec.top[k.name]:
                        continue
                    table, held = rec.tables[k.name], rec.held[k.name]
                    for page_no in range(rec.top[k.name] + 1, top + 1):
                        col = page_no % k.columns
                        page = int(table[col])
                        if page != TRASH_PAGE:
                            with self._lock:
                                k.allocator.release(page)
                                held.remove(page)
                            table[col] = TRASH_PAGE
                            released += 1
                    rec.top[k.name] = top
            if released:
                with self._lock:
                    self._counts['window_pages_released'] += released

    def drop(self, rec):
        """Give back every hold of ``rec`` (caller holds the lock).
        Pages whose registration still holds a reference stay for
        later hits, until eviction."""
        for k in self._kinds:
            held = rec.held[k.name]
            for page in held:
                k.allocator.release(page)
            rec.held[k.name] = []
        self._stateful.discard(rec.slot)

    def reset(self):
        """The device pools were rebuilt: free lists, reference counts
        and registrations of every kind describe garbage now. Callers
        retire (and :meth:`drop`) the sequences in flight first."""
        with self._lock:
            for k in self._kinds:
                k.allocator.reset()
                if k.prefix is not None:
                    k.prefix.clear()
            self._stateful.clear()

    def _alloc(self, k, n, slot):
        """``n`` fresh pages of kind ``k``, evicting its least recently
        used registered prefixes under pool pressure; None on
        exhaustion (the caller fails typed)."""
        with self._lock:
            ids = k.allocator.alloc(n)
            evicted = []
            if ids is None and k.prefix is not None:
                evicted = k.prefix.evict_lru(n)
                ids = k.allocator.alloc(n)
            if evicted:
                self._counts['page_evictions'] += len(evicted)
        for page in evicted:
            self._event('page_evict', page=page, slot=slot)
        if ids is not None and slot is not None:
            self._event('page_alloc', pages=len(ids), slot=slot)
        return ids

    # -- what the programs are handed --------------------------------------

    def tables(self, slots, placed):
        """The tables of one step: ``placed`` is ``(slot, record)`` of
        every sequence that steps; rows of the other slots hold the
        trash page. Built anew every tick, from zeros: keeping one
        table across ticks and sending the device only the rows a page
        fault changed belongs here (this owner sees every change of a
        row), and is a measured change of its own (ROADMAP S1)."""
        out = {k.name: onp.zeros((slots, k.columns), 'int32')
               for k in self._kinds}
        for slot, rec in placed:
            for name, table in out.items():
                table[slot] = rec.tables[name]
        return self._out(out)

    def step_pages(self, slots, positions):
        """What one step's attention has to read and what a gathered
        view holds, in pages over all attention layers: ``positions``
        are the live sequences' newest positions. A sequence at
        position ``p`` has ``p // page_size + 1`` live pages a full
        layer (a ring no more than its columns): what attention that
        walks the table reads (:func:`walks_pages`). The view is
        ``slots`` x table columns a layer, whatever is live. Returns
        (walked, view): the scheduler books them as ``kv_pages_walked``
        and ``kv_pages_view``, whose ratio is the share of the view
        that was live."""
        tops = [p // self.page_size + 1 for p in positions]
        walked = sum(k.layers * sum(min(t, k.columns) for t in tops)
                     for k in self._kinds)
        return walked, slots * self._view_pages

    def step_copies(self, tables, positions):
        """The copies one step's walks issue over all attention layers,
        a K and a V page counted once as :meth:`step_pages` counts
        them: ``tables`` as :meth:`tables` handed them out and
        ``positions`` (slots,) are the step's own operands, and the
        rule is the kernel's (``ops.pallas.attention.walk_copy_runs``:
        a chunk of consecutive pages is one copy). 0 where the step
        gathers. The scheduler books it as ``kv_page_copies``:
        ``kv_pages_walked`` over it is the pages a copy moves."""
        if self._walk_block is None:
            return 0
        from ...ops.pallas.attention import walk_copy_runs
        # columns past the highest live page hold no copy
        live = int(positions.max()) // self.page_size + 1
        _run, copies = walk_copy_runs(
            onp, tables[:, :live], positions, self.page_size,
            self._walk_block, TRASH_PAGE)
        return self._kinds[0].layers * int(copies.sum())

    def first_pages(self, rec, n_tokens):
        """The pages that hold positions ``[0, n_tokens)`` of ``rec``
        (a live migration's export; a ring has given its first pages
        back, and ``export_pages`` refuses a cache that has one before
        it reads these)."""
        npages = pages_for(n_tokens, self.page_size)
        return self._out({k.name: [int(p) for p in
                                   rec.tables[k.name][:npages]]
                          for k in self._kinds})

    # -- figures (caller holds the lock) -----------------------------------

    def pool_stats(self):
        """The full layers' pool: what the page gauges, the cache
        accounting's ``pool`` and a ``serve_reject`` report."""
        return self._kinds[0].allocator.stats()

    def stats(self):
        """``stats()``'s blocks: ``pages`` for the full layers' pool,
        ``pages_<kind>`` for every other kind's."""
        out = {}
        for k in self._kinds:
            block = k.allocator.stats()
            if k.prefix is not None:
                block['prefix_entries'] = len(k.prefix)
            out['pages' if k is self._kinds[0]
                else 'pages_%s' % k.name] = block
        return out

    def live_gauges(self):
        """Pages in use by kind of layer, sequences' holds and the
        registry's alike (gauges, not sums), where there is more than
        one kind; ``state_bytes_live``, the slot entries' bytes of the
        sequences in flight, where the cache has slot entries."""
        out = {}
        if len(self._kinds) > 1:
            out = {'pages_live.%s' % k.name: k.allocator.used_pages
                   for k in self._kinds}
        if self._state_bytes:
            out['state_bytes_live'] = \
                len(self._stateful) * self._state_bytes
        return out

    def held_bytes(self, recs):
        """Device bytes behind the holds of ``recs``: a page of the
        window layers and a page of the full layers hold different
        bytes, and a sequence in flight holds its slot's state rows."""
        held = dict.fromkeys((k.name for k in self._kinds), 0)
        live = 0
        for rec in recs:
            live += 1
            for name, pages in rec.held.items():
                held[name] += len(pages)
        return pool_bytes(self._spec, held['full'], held.get('window', 0),
                          live)
