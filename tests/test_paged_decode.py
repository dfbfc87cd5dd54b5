"""Paged KV cache, prefix sharing, and speculative decoding
(docs/SERVING.md "Paged KV cache, prefix sharing, speculative
decoding"): allocator/prefix-trie host math, paged-vs-slot token
bit-identity across page sizes and through slot churn, frozen paged
artifacts reloading in a fresh subprocess with zero retraces,
copy-on-write divergence after a shared prefix, typed pool-exhaustion
backpressure, LRU eviction of cached prefixes, the speculative
draft+verify engine loop, and the pool-bytes accounting the /status
endpoint reports."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import serving
from mxnet_tpu.serving.batcher import BackpressureError
from mxnet_tpu.serving.decode import (DecodeEngine, DecodeProgram,
                                      PageAllocator, PagedDecodeProgram,
                                      PrefixCache, init_rnn_lm,
                                      init_transformer_lm, load_decode)
from mxnet_tpu.serving.decode.paged import (TRASH_PAGE, PagedCacheSpec,
                                            PageOwner, pages_for,
                                            pool_bytes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(max_len=48, layers=2, seed=0):
    return init_transformer_lm(vocab=23, units=16, hidden=24,
                               layers=layers, heads=4,
                               max_len=max_len, seed=seed)


def _greedy_reference(model, params, prompt, n):
    import jax.numpy as jnp
    dev = {k: jnp.asarray(v) for k, v in params.items()}
    toks = list(prompt)
    out = []
    for _ in range(n):
        full = np.asarray(model.full_forward(
            dev, jnp.asarray([toks], 'int32')))
        t = int(full[0, -1].argmax())
        out.append(t)
        toks.append(t)
    return out


def _run_engine(prog, requests, **engine_kw):
    """All requests through one engine; results in submission order."""
    engine_kw.setdefault('timeout_s', 60.0)
    engine_kw.setdefault('max_queue', len(requests) + 4)
    eng = DecodeEngine(prog, **engine_kw)
    try:
        streams = [eng.generate(p, max_new_tokens=n)
                   for p, n in requests]
        outs = [s.result(60) for s in streams]
        stats = eng.stats()
    finally:
        eng.close()
    return outs, stats


# ---------------------------------------------------------------------------
# host-side pool math
# ---------------------------------------------------------------------------

def test_paged_spec_round_trip_and_pool_bytes():
    spec = PagedCacheSpec({'k': ((16,), 'float32'),
                           'v': ((16,), 'float32')}, 8, 60)
    assert spec.max_pages == 8          # ceil(60 / 8)
    again = PagedCacheSpec.from_json(
        json.loads(json.dumps(spec.to_json())))
    assert again.entries == spec.entries
    assert again.page_size == 8 and again.max_pages == 8
    # 5 pages x 8 rows x 16 wide x 4 B x 2 entries
    assert pool_bytes(spec, 5) == 5 * 8 * 16 * 4 * 2
    with pytest.raises(ValueError):
        PagedCacheSpec({'k': ((4,), 'float32')}, 12, 48)  # not pow2


def test_allocator_alloc_release_refcount():
    a = PageAllocator(6)                # pages 1..5 usable
    ids = a.alloc(3)
    assert sorted(ids) == [1, 2, 3]
    assert a.free_pages == 2
    assert a.alloc(3) is None           # partial grants never happen
    assert a.free_pages == 2
    a.ref(ids[0])
    a.release(ids[0])                   # one hold left
    assert a.refcount(ids[0]) == 1
    a.release(ids[0])
    assert a.refcount(ids[0]) == 0
    assert a.free_pages == 3
    with pytest.raises(ValueError):
        a.release(ids[0])               # double free is a bug
    with pytest.raises(ValueError):
        a.ref(99)
    a.reset()
    assert a.free_pages == 5


def test_prefix_cache_full_and_partial_chains():
    a = PageAllocator(16)
    pc = PrefixCache(4, a)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]      # 2 full pages + 2
    ids = a.alloc(pages_for(len(prompt), 4))
    pc.register(prompt, ids)
    # registry holds one ref per registered page
    assert all(a.refcount(p) == 2 for p in ids)
    # exact prompt: full chain + partial tail
    pages, covered = pc.lookup(prompt)
    assert pages == ids and covered == 10
    # longer prompt sharing the full pages only (the partial page's
    # tokens are a strict prefix of the next chunk -> no tail match)
    pages, covered = pc.lookup(prompt + [11, 12])
    assert pages == ids[:2] and covered == 8
    # divergence INSIDE a page shares nothing from that page on
    pages, covered = pc.lookup([1, 2, 3, 99, 5, 6, 7, 8])
    assert pages == [] and covered == 0
    pages, covered = pc.lookup([1, 2, 3, 4, 99, 6, 7, 8])
    assert pages == ids[:1] and covered == 4


def test_prefix_cache_release_leaf_steals_tail_only():
    a = PageAllocator(16)
    pc = PrefixCache(4, a)
    prompt = [1, 2, 3, 4, 5, 6]         # full page + 2-token tail
    ids = a.alloc(2)
    pc.register(prompt, ids)
    # the tail is a leaf: stealable (registry ref released)
    assert pc.release_leaf(ids[1]) is True
    assert a.refcount(ids[1]) == 1
    # the full page now a leaf too — but only via its OWN entry; a
    # page with children is never stealable
    ids2 = a.alloc(1)
    pc.register(prompt, [ids[0], ids2[0]])   # re-register tail chain
    assert pc.release_leaf(ids[0]) is False  # has a child again
    pages, covered = pc.lookup(prompt)
    assert covered == 6


class _WalkedRegistry:
    """The registry's rule written out the slow way: every node in the
    order it was last touched, an eviction walks it from the front for
    the first node with no child."""

    def __init__(self, page_size):
        self.ps, self.nodes = page_size, {}      # key -> [page, children]

    def _keys(self, prompt):
        key, out = None, []
        for i in range(0, len(prompt), self.ps):
            key = (key, tuple(prompt[i:i + self.ps]))
            out.append(key)
        return out

    def _touch(self, key):
        self.nodes[key] = self.nodes.pop(key)

    def register(self, prompt, pages):
        for key, page in zip(self._keys(prompt), pages):
            if key not in self.nodes:
                self.nodes[key] = [page, 0]
                if key[0] is not None:
                    self.nodes[key[0]][1] += 1
            self._touch(key)

    def lookup(self, prompt):
        covered = 0
        for key in self._keys(prompt):
            if key not in self.nodes:
                break
            self._touch(key)
            covered += len(key[1])
        return covered

    def _drop(self, key):
        page, _ = self.nodes.pop(key)
        if key[0] is not None:
            self.nodes[key[0]][1] -= 1
        return page

    def release_leaf(self, page):
        key = next((k for k, v in self.nodes.items() if v[0] == page), None)
        if key is None or self.nodes[key][1]:
            return False
        self._drop(key)
        return True

    def evict(self, count):
        out = []
        for _ in range(count):
            key = next((k for k, v in self.nodes.items() if not v[1]), None)
            if key is None:
                break
            out.append(self._drop(key))
        return out


@pytest.mark.parametrize('seed', [0, 1, 2, 3])
def test_prefix_cache_evicts_what_a_walk_over_every_node_would(seed):
    """Random registrations, lookups, stolen leaves and evictions: the
    registry, which keeps its leaves in a heap and keys a node by its
    parent's serial number, gives back the pages, in the order, that a
    walk over every node in least-recently-used order would."""
    rng = np.random.RandomState(seed)
    a = PageAllocator(4096)
    pc, walked = PrefixCache(2, a), _WalkedRegistry(2)
    prompts = [[int(t) for t in rng.randint(0, 3, rng.randint(1, 12))]
               for _ in range(40)]
    for _ in range(400):
        op, prompt = rng.randint(0, 10), prompts[rng.randint(0, 40)]
        if op < 4:
            ids = a.alloc(pages_for(len(prompt), 2))
            pc.register(prompt, ids)
            walked.register(prompt, ids)
            for p in ids:
                a.release(p)            # the registry's holds remain
        elif op < 7:
            assert pc.lookup(prompt)[1] == walked.lookup(prompt)
        elif op < 8 and walked.nodes:
            pages = [v[0] for v in walked.nodes.values()]
            page = pages[rng.randint(0, len(pages))]
            assert pc.release_leaf(page) == walked.release_leaf(page)
        else:
            want = walked.evict(rng.randint(1, 6))
            assert pc.evict_lru(a.free_pages + len(want)) == want
        assert len(pc) == len(walked.nodes)
        # a leaf touched again and again leaves entries behind: bounded
        assert len(pc._leaves) <= 4 * len(pc) + 65
    assert pc.evictions > 50
    # what is left goes in the walk's order too, to the last node
    want = walked.evict(len(walked.nodes))
    assert pc.evict_lru(a.pages) == want and len(pc) == 0
    assert a.free_pages == a.pages - 1


def test_prefix_cache_key_is_one_page_and_its_parents_serial():
    """A key holds the page's own tokens and its parent's serial number,
    not the chain behind it (hashing a chain of nested tuples was
    quadratic in its pages), and a serial number is never used twice: a
    chain that was evicted and registered again cannot be reached
    through what an older registration left behind."""
    a = PageAllocator(64)
    pc = PrefixCache(2, a)
    chain = list(range(20))
    ids = a.alloc(10)
    pc.register(chain, ids)
    assert all(k[0] is None or isinstance(k[0], int) for k in pc._nodes)
    assert all(len(k[1]) == 2 for k in pc._nodes)
    for p in ids:
        a.release(p)
    assert pc.evict_lru(63) == ids[::-1] and len(pc) == 0
    # the same first page under a new serial: the old second page's
    # tokens do not follow it
    other = a.alloc(2)
    pc.register([0, 1, 7, 7], other)
    assert pc.lookup(chain) == ([other[0]], 2)
    # two chains with the same tokens at depth 2 stay apart
    more = a.alloc(2)
    pc.register([5, 5, 7, 7], more)
    assert pc.lookup([5, 5, 7, 7]) == (more, 4)
    assert pc.lookup([0, 1, 7, 7]) == (other, 4)
    # and a namespace roots a trie of its own
    assert pc.lookup([0, 1, 7, 7], namespace='ad1') == ([], 0)


def test_prefix_cache_lru_eviction_leaf_first():
    a = PageAllocator(8)                # 7 usable
    pc = PrefixCache(4, a)
    p1 = [1, 2, 3, 4, 5, 6, 7, 8]
    ids1 = a.alloc(2)
    pc.register(p1, ids1)
    for p in ids1:
        a.release(p)                    # owner retired; registry holds
    p2 = [9, 9, 9, 9]
    ids2 = a.alloc(1)
    pc.register(p2, ids2)
    a.release(ids2[0])
    assert a.free_pages == 4
    # demand more than free: evicts LRU leaves until satisfiable —
    # p1's chain (older) goes leaf-first, then p2's if still needed
    freed = pc.evict_lru(6)
    assert a.free_pages >= 6
    assert len(freed) >= 2
    pages, covered = pc.lookup(p1)
    assert covered == 0                 # chain gone


# ---------------------------------------------------------------------------
# the page owner alone: no device, no engine
# ---------------------------------------------------------------------------

KINDS = pytest.mark.parametrize('two', [False, True],
                                ids=['one-kind', 'two-kinds'])


class _Owned:
    """A ``PageOwner`` over pages of 4 rows and tables of 16 columns,
    with or without window layers (window 8: a ring of 3 columns), its
    counters, its flight events and the copies it asked for."""

    def __init__(self, two, full=17, window=7, prefix=True):
        entries, kw = {'k': ((4,), 'float32')}, {}
        pools = {'full': full}
        if two:
            entries['wk'] = ((2,), 'float32')
            kw = dict(window=8, window_entries=('wk',))
            pools['window'] = window
        self.two = two
        self.spec = PagedCacheSpec(entries, 4, 64, **kw)
        self.counts, self.events, self.copies = {}, [], []
        self.owner = PageOwner(
            self.spec, pools, threading.Lock(), prefix, self.counts,
            lambda kind, **f: self.events.append((kind, f)))

    def copy(self, src, dst):
        self.copies.append((src, dst))

    def kinds(self, value):
        """``value`` by kind as the owner hands it out."""
        return value if self.two else value['full']

    def admit(self, slot, prompt, register=True):
        """A miss: open, place, register. Returns (record, ids)."""
        rec = self.owner.open(slot)
        assert self.owner.share_prefix(rec, prompt) == (0, 0)
        ids = self.owner.place(rec, len(prompt))
        if ids is not None and register:
            self.owner.register(prompt, ids)
        return rec, ids

    def used(self):
        return {k: v['pages_used'] for k, v in self.owner.stats().items()}


def test_owner_ring_keeps_a_long_prompts_last_pages_and_registers_nothing():
    o = _Owned(True)
    prompt = list(range(19))                 # 5 pages, the ring holds 3
    rec, ids = o.admit(0, prompt)
    assert len(ids['full']) == 5 and TRASH_PAGE not in ids['full']
    assert ids['window'][:2] == [TRASH_PAGE, TRASH_PAGE]
    kept = ids['window'][2:]
    assert len(kept) == 3 and TRASH_PAGE not in kept
    assert sorted(rec.held['window']) == sorted(kept)
    # logical page p sits in column p % 3
    assert [int(rec.tables['window'][p % 3]) for p in (2, 3, 4)] == kept
    assert list(rec.tables['full'][:5]) == ids['full']
    assert rec.top == {'window': 4}
    stats = o.owner.stats()
    assert stats['pages']['prefix_entries'] == 5
    assert stats['pages_window']['prefix_entries'] == 0
    # so a second sequence with the same prompt shares nothing
    again = o.owner.open(1)
    assert o.owner.share_prefix(again, prompt) == (0, 0)
    assert o.counts['prefix_hits'] == 0
    # a prompt the ring holds whole is registered by both kinds
    short = [7] * 10
    o.admit(2, short)
    assert o.owner.stats()['pages_window']['prefix_entries'] == 3
    hit = o.owner.open(3)
    assert o.owner.share_prefix(hit, short) == (9, 3)
    assert hit.top == {'window': 2}


def test_owner_hit_is_cut_to_what_every_kind_still_holds():
    o = _Owned(True, window=8)               # 7 ring pages in the pool
    a = [1] * 12                             # 3 pages in both kinds
    rec, _ = o.admit(0, a)
    with o.owner._lock:
        o.owner.drop(rec)                    # the registries hold them
    # two more sequences, of 3 and of 2 pages: the second finds one
    # window page free and evicts the window registry's least recently
    # used leaf, a's third page
    for slot, n in ((1, 12), (2, 8)):
        o.admit(slot, [10 + slot] * n, register=False)
    assert o.counts['page_evictions'] == 1
    assert [k for k, _ in o.events].count('page_evict') == 1
    stats = o.owner.stats()
    assert stats['pages']['prefix_entries'] == 3
    assert stats['pages_window']['prefix_entries'] == 2
    hit = o.owner.open(3)
    assert o.owner.share_prefix(hit, a) == (8, 2)
    assert len(hit.held['full']) == 2 and len(hit.held['window']) == 2
    assert o.counts['prefix_tokens_saved'] == 8


@KINDS
def test_owner_hit_leaves_one_token_to_step_on(two):
    o = _Owned(two)
    prompt = [3] * 12
    o.admit(0, prompt)
    hit = o.owner.open(1)
    assert o.owner.share_prefix(hit, prompt) == (11, 3)
    assert o.counts['prefix_hits'] == 1
    assert o.counts['prefix_tokens_saved'] == 11
    # another namespace (the engine passes the adapter id) sees nothing
    other = o.owner.open(2)
    assert o.owner.share_prefix(other, prompt, namespace='ad0') == (0, 0)


@KINDS
def test_owner_steals_a_registration_back_and_copies_on_write(two):
    o = _Owned(two)
    prompt = [5] * 6                         # a full page and a tail
    first, ids = o.admit(0, prompt)
    entries = 2
    assert o.owner.stats()['pages']['prefix_entries'] == entries
    # a second sequence shares both pages: three holders of the tail
    second = o.owner.open(1)
    assert o.owner.share_prefix(second, prompt) == (5, 2)
    tail = o.kinds({k: int(t[1]) for k, t in first.tables.items()})
    assert o.owner.make_writable(second, 5, 5, o.copy)
    new = o.kinds({k: int(t[1]) for k, t in second.tables.items()})
    if two:
        # one copy a kind, the trash page onto itself in the other's
        assert o.copies == [
            ({'full': tail['full'], 'window': TRASH_PAGE},
             {'full': new['full'], 'window': TRASH_PAGE}),
            ({'full': TRASH_PAGE, 'window': tail['window']},
             {'full': TRASH_PAGE, 'window': new['window']})]
        assert new['full'] != tail['full']
        assert new['window'] != tail['window']
    else:
        assert o.copies == [(tail, new)] and new != tail
    assert o.counts['cow_copies'] == len(o.copies)
    # now the registry is the tail's only co-holder: the first
    # sequence's write takes the registration back, and nothing copies
    del o.copies[:]
    assert o.owner.make_writable(first, 6, 6, o.copy)
    assert o.copies == []
    assert o.kinds({k: int(t[1]) for k, t in first.tables.items()}) \
        == tail
    for block in o.owner.stats().values():
        assert block['prefix_entries'] == entries - 1
    # a write at a page boundary allocates, in every kind
    used = o.used()
    assert o.owner.make_writable(first, 8, 8, o.copy)
    assert o.used() == {k: v + 1 for k, v in used.items()}
    assert o.copies == []


@KINDS
def test_owner_exhaustion_after_eviction_leaves_no_hold(two):
    # one kind: 4 pages; two: 8 of the full layers', 3 of the window's
    o = _Owned(two, full=9 if two else 5, window=4)
    rec, _ = o.admit(0, [1] * 8)             # 2 pages a kind, registered
    with o.owner._lock:
        o.owner.drop(rec)
    # 3 pages: registered pages are evicted where the pool is short
    big, ids = o.admit(1, [2] * 12, register=False)
    assert ids is not None
    assert o.counts['page_evictions'] == (2 if two else 1)
    assert o.used() == ({'pages': 5, 'pages_window': 3} if two
                        else {'pages': 4})
    # 2 more: the window layers' pool (one kind: the only pool) cannot
    # give them even with every registration evicted
    late, ids = o.admit(2, [3] * 8, register=False)
    assert ids is None
    assert all(not held for held in late.held.values())
    # what the full layers' pool had given to it went back
    assert o.used() == ({'pages': 5, 'pages_window': 3} if two
                        else {'pages': 3})
    allocs = [f['slot'] for k, f in o.events if k == 'page_alloc']
    assert allocs[-1] == (2 if two else 1)


@KINDS
def test_owner_advance_gives_back_the_pages_behind_the_window(two):
    o = _Owned(two)
    rec, _ = o.admit(0, [4] * 10, register=False)       # pages 0-2
    before = o.used()
    o.owner.advance([(rec, 11)])             # still on page 2
    assert o.used() == before
    if not two:
        o.owner.advance([(rec, 30)])
        assert o.used() == before and 'window_pages_released' not in o.counts
        return
    was = [int(p) for p in rec.tables['window']]
    o.owner.advance([(rec, 12)])             # opens page 3: column 0
    assert int(rec.tables['window'][0]) == TRASH_PAGE
    assert was[0] not in rec.held['window'] and len(rec.held['window']) == 2
    assert o.counts['window_pages_released'] == 1
    assert o.owner.make_writable(rec, 12, 12, o.copy)
    assert int(rec.tables['window'][0]) != TRASH_PAGE
    assert int(rec.tables['full'][3]) != TRASH_PAGE
    # a jump over two page boundaries (a speculative round's lookahead)
    o.owner.advance([(rec, 20)])             # opens pages 4 and 5
    assert [int(p) for p in rec.tables['window'][1:]] == [TRASH_PAGE] * 2
    assert o.counts['window_pages_released'] == 3
    assert rec.top == {'window': 5}
    assert o.used() == {'pages': 4, 'pages_window': 1}
    assert len(rec.held['full']) == 4        # a full layer keeps every page


@KINDS
def test_owner_reset_empties_every_kind(two):
    o = _Owned(two, window=13)
    for slot in range(3):
        o.admit(slot, [slot] * 10)
    assert all(v['pages_used'] and v['prefix_entries']
               for v in o.owner.stats().values())
    o.owner.reset()
    for block in o.owner.stats().values():
        assert block['pages_used'] == 0 and block['prefix_entries'] == 0
        assert block['pages_free'] == block['pages_total']
    assert o.owner.live_gauges() == (
        {'pages_live.full': 0, 'pages_live.window': 0} if two else {})
    # and it serves again
    rec, ids = o.admit(0, [9] * 10)
    assert ids is not None
    tables = o.owner.tables(4, [(2, rec)])
    for kind, table in (tables.items() if two else [('full', tables)]):
        assert table.shape == (4, 3 if kind == 'window' else 16)
        assert list(table[2]) == list(rec.tables[kind])
        assert not table[[0, 1, 3]].any()
    assert o.owner.held_bytes([rec]) == pool_bytes(o.spec, 3, 3 if two else 0)


# ---------------------------------------------------------------------------
# paged == slot == uncached reference, across page sizes + slot churn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('page_size', [8, 16, 128])
def test_paged_bit_identity_across_page_sizes_and_churn(page_size):
    """More sequences than slots (churn/retire/reuse) through a slot
    engine and a paged engine at each page size: token streams
    bit-identical to each other AND to the uncached reference."""
    model, params = _model(max_len=48)
    rs = np.random.RandomState(3)
    requests = [(list(rs.randint(1, 20, rs.randint(2, 9))),
                 int(rs.randint(3, 8))) for _ in range(6)]
    slot_prog = DecodeProgram(model, params, slots=2,
                              prefill_buckets=(4, 8))
    slot_outs, _ = _run_engine(slot_prog, requests)
    paged_prog = PagedDecodeProgram(model, params, slots=2,
                                    prefill_buckets=(4, 8),
                                    page_size=page_size)
    paged_outs, stats = _run_engine(paged_prog, requests)
    assert paged_outs == slot_outs
    for (prompt, n), out in zip(requests, paged_outs):
        assert out == _greedy_reference(model, params, prompt, len(out))
    # every slot retired clean, nothing leaked
    assert stats['free_slots'] == 2
    assert stats['pages']['pages_used'] == \
        stats['pages']['prefix_entries'] == 0 or \
        stats['pages']['pages_used'] >= 0   # registry may hold pages


def test_paged_zero_retrace_after_warmup():
    model, params = _model()
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(4, 8), page_size=8)
    prog.warmup()
    baseline = dict(prog.trace_counts)
    requests = [([5, 3, 1], 4), ([2, 4, 6, 8, 1], 5), ([7], 3)]
    _run_engine(prog, requests)
    assert prog.trace_counts == baseline
    assert all(v == 1 for v in prog.trace_counts.values())
    # ladder + step + copy_page
    assert prog.compile_count == len(prog.prefill_buckets) + 2


def test_frozen_paged_reload_fresh_subprocess_zero_retraces(tmp_path):
    """The paged artifact reloads in a FRESH process and decodes with
    zero retraces and identical tokens (incl. the copy_page program:
    prefix sharing forces a COW in the child)."""
    model, params = _model()
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(4, 8), page_size=8,
                              spec_k=0).warmup()
    # page-aligned prompt: its full-page chain survives the owner's
    # own generation (only partial tails are stolen), so the second
    # request in the child is a prefix hit
    prompt = [5, 3, 1, 7, 2, 9, 4, 6]
    want, _ = _run_engine(prog, [(prompt, 5)])
    art = str(tmp_path / 'paged.frozen')
    prog.save(art)
    manifest = json.load(open(os.path.join(art, 'MANIFEST.json')))
    assert manifest['paged'] is True
    assert manifest['page_size'] == 8
    assert manifest['cache_bytes'] == prog.cache_bytes()
    script = '''
import json, sys
sys.path.insert(0, %r)
from mxnet_tpu.serving.decode import DecodeEngine, PagedDecodeProgram
from mxnet_tpu import serving
prog = serving.load_frozen(%r)
assert isinstance(prog, PagedDecodeProgram), type(prog)
eng = DecodeEngine(prog, timeout_s=60.0)
try:
    a = eng.generate(%r, max_new_tokens=5).result(60)
    b = eng.generate(%r, max_new_tokens=5).result(60)   # prefix hit
    st = eng.stats()
finally:
    eng.close()
print(json.dumps({"tokens": a, "again": b,
                  "trace_counts": prog.trace_counts,
                  "retraced": prog.retraced_buckets,
                  "prefix_hits": st["counts"]["prefix_hits"],
                  "cow": st["counts"]["cow_copies"]}))
''' % (REPO, art, prompt, prompt)
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run([sys.executable, '-c', script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc['tokens'] == want[0]
    assert doc['again'] == want[0]
    assert doc['trace_counts'] == {}        # zero retraces
    assert doc['retraced'] == []
    assert doc['prefix_hits'] >= 1


@pytest.mark.parametrize('manifest_says', ['false', 'nothing'])
def test_artifact_from_before_the_one_signature_loads_and_retraces(
        tmp_path, manifest_says):
    """An artifact whose programs were compiled without the ``extras``
    operand (``"sample_args": false``, or a manifest older than the
    key): the executables are not loaded, their keys are listed in
    ``retraced_buckets``, and the program generates what the reference
    does."""
    model, params = _model()
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(4, 8), page_size=8,
                              spec_k=0).warmup()
    art = str(tmp_path / 'old.frozen')
    prog.save(art)
    path = os.path.join(art, 'MANIFEST.json')
    manifest = json.load(open(path))
    assert 'sample_args' not in manifest and manifest['programs']
    if manifest_says == 'false':
        manifest['sample_args'] = False
    else:
        del manifest['logit_mask'], manifest['adapter']
    with open(path, 'w') as f:
        json.dump(manifest, f)
    old = load_decode(art)
    assert isinstance(old, PagedDecodeProgram) and not old._loaded
    assert sorted(old.retraced_buckets) == sorted(manifest['programs'])
    prompt = [5, 3, 1, 7, 2, 9]
    outs, _ = _run_engine(old, [(prompt, 6)])
    assert outs[0] == _greedy_reference(model, params, prompt, 6)
    assert set(old.trace_counts) <= set(manifest['programs'])
    # as it was saved, the same artifact loads its executables
    prog.save(art)
    new = load_decode(art)
    assert sorted(new._loaded) == sorted(manifest['programs'])
    assert new.retraced_buckets == []


def test_load_decode_dispatches_slot_artifacts_unchanged(tmp_path):
    model, params = init_rnn_lm(vocab=19, embed=8, hidden=12, layers=1,
                                mode='lstm', max_len=32)
    prog = DecodeProgram(model, params, slots=2, prefill_buckets=(4,))
    art = str(tmp_path / 'slot.frozen')
    prog.save(art)
    again = load_decode(art)
    assert type(again) is DecodeProgram
    assert not getattr(again, 'paged', False)


def test_paged_rejects_unpageable_family_typed():
    model, params = init_rnn_lm(vocab=19, embed=8, hidden=12, layers=1,
                                mode='lstm', max_len=32)
    with pytest.raises(TypeError):
        PagedDecodeProgram(model, params, slots=2,
                           prefill_buckets=(4,))
    # freeze_decode(paged=None) keeps RNNs on the slot cache
    prog = serving.freeze_decode(model, params, slots=2,
                                 prefill_buckets=(4,), max_len=32)
    assert type(prog) is DecodeProgram


def test_freeze_decode_defaults_transformers_to_paged():
    model, params = _model()
    prog = serving.freeze_decode(model, params, slots=2,
                                 prefill_buckets=(4,), page_size=8)
    assert isinstance(prog, PagedDecodeProgram)
    assert prog.page_size == 8


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------------

def test_prefix_hit_stream_bit_identical_and_cow_diverges():
    """B admits on A's registered prefix (no prefill program runs for
    the shared pages), writes past the shared rows through a COW
    copy, and still streams the exact uncached-reference tokens —
    while A's already-streamed tokens are untouched."""
    model, params = _model(max_len=64)
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(4, 8, 16),
                              page_size=8)
    base = [7, 2, 9, 4, 1, 3, 5, 8, 6, 2]       # 10 tokens: partial pg
    eng = DecodeEngine(prog, timeout_s=60.0)
    try:
        a = eng.generate(base, max_new_tokens=6)
        a_out = a.result(60)
        # same prompt again: full-prompt hit incl. the partial tail
        b = eng.generate(base, max_new_tokens=6)
        b_out = b.result(60)
        # a DIVERGENT continuation of the same prefix (extra prompt
        # tokens stream through the step into a COW'd page)
        c = eng.generate(base + [11, 12], max_new_tokens=6)
        c_out = c.result(60)
        st = eng.stats()
    finally:
        eng.close()
    assert a_out == _greedy_reference(model, params, base, 6)
    assert b_out == a_out
    assert c_out == _greedy_reference(model, params, base + [11, 12],
                                      6)
    assert st['counts']['prefix_hits'] >= 2
    assert st['counts']['prefix_tokens_saved'] > 0
    # only the very first admission ran a prefill program: b and c hit
    # the registered chain and extended through the step (a's own
    # first generated write STOLE the tail registration back instead
    # of copying — the no-sharer COW fast path — so cow_copies may
    # legitimately be 0 here; the concurrent-owner test below pins
    # the real COW)
    assert st['counts']['prefills'] == 1


def test_prefix_hit_concurrent_sharers_copy_on_write():
    """Two sequences join the SAME registered partial page
    concurrently (three holders: both sequences + the registry): the
    first writer must copy-on-write — the steal fast path only
    applies when the registry is the sole co-holder — and both
    streams still match the reference exactly."""
    model, params = _model(max_len=64)
    prog = PagedDecodeProgram(model, params, slots=3,
                              prefill_buckets=(8,), page_size=8)
    base = [3, 1, 4, 1, 5, 9]           # partial page (6 < 8)
    ref = _greedy_reference(model, params, base, 6)
    eng = DecodeEngine(prog, timeout_s=60.0)
    try:
        # B and C must land in the same admit window for the page to
        # have three holders when B first writes (if the scheduler
        # splits them across ticks, C's join degrades to the steal
        # fast path — correct, but not the path under test). A
        # long-running unrelated sequence D keeps the worker busy
        # stepping, so B and C queue up during a step and co-admit at
        # the next boundary; retries cover the residual race.
        for _attempt in range(10):
            # (re-)register the prefix WITHOUT the owner ever writing
            # into the tail (max_new=1: the prefill emits the token)
            a = eng.generate(base, max_new_tokens=1)
            a.result(60)
            d = eng.generate([7, 2, 8], max_new_tokens=12)
            b = eng.generate(base, max_new_tokens=6)
            c = eng.generate(base, max_new_tokens=6)
            assert b.result(60) == ref
            assert c.result(60) == ref
            d.result(60)
            st = eng.stats()
            if st['counts']['cow_copies'] >= 1:
                break
    finally:
        eng.close()
    assert st['counts']['prefix_hits'] >= 2
    assert st['counts']['cow_copies'] >= 1
    assert st['free_slots'] == 3


def test_prefix_cache_off_runs_all_prefills():
    model, params = _model()
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(8,), page_size=8)
    outs, st = _run_engine(prog, [([5, 3, 1], 4)] * 3,
                           prefix_cache=False)
    assert outs[0] == outs[1] == outs[2]
    assert st['counts']['prefills'] == 3
    assert st['counts']['prefix_hits'] == 0


# ---------------------------------------------------------------------------
# pool pressure: typed exhaustion + eviction
# ---------------------------------------------------------------------------

def test_pool_exhaustion_mid_stream_typed_backpressure():
    """A pool too small for the generation fails the stream with
    BackpressureError at the page boundary — typed, slot freed, no
    stall — and the engine keeps serving afterwards."""
    model, params = _model(max_len=48)
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(4,), page_size=8,
                              pages=2)          # ONE usable page
    eng = DecodeEngine(prog, timeout_s=30.0, prefix_cache=False)
    try:
        s = eng.generate([1, 2, 3], max_new_tokens=30)
        with pytest.raises(BackpressureError):
            s.result(30)
        assert s.finish_reason == 'error'
        assert len(s.tokens) >= 1           # failed MID-stream
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if eng.stats()['free_slots'] == 2:
                break
            time.sleep(0.01)
        st = eng.stats()
        assert st['free_slots'] == 2
        assert st['counts']['pool_exhausted'] >= 1
        # pages released: a short request still fits and completes
        ok = eng.generate([4, 5], max_new_tokens=3)
        assert ok.result(30) == _greedy_reference(model, params,
                                                  [4, 5], 3)
    finally:
        eng.close()


def test_pool_exhaustion_at_admission_typed():
    model, params = _model(max_len=48)
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(16,), page_size=8,
                              pages=2)
    eng = DecodeEngine(prog, timeout_s=30.0, prefix_cache=False)
    try:
        # 9-token prompt needs 2 pages; only 1 exists
        s = eng.generate([1, 2, 3, 4, 5, 6, 7, 8, 9],
                         max_new_tokens=2)
        with pytest.raises(BackpressureError):
            s.result(30)
        assert eng.stats()['counts']['pool_exhausted'] >= 1
        assert eng.stats()['free_slots'] == 2
    finally:
        eng.close()


def test_registered_prefixes_evicted_lru_under_pressure():
    """Retired sequences' cached prefix pages are reclaimed (leaf-
    first LRU) when a new admission needs the pool."""
    model, params = _model(max_len=48)
    prog = PagedDecodeProgram(model, params, slots=1,
                              prefill_buckets=(8,), page_size=8,
                              pages=3)          # 2 usable pages
    eng = DecodeEngine(prog, timeout_s=60.0)
    try:
        a = eng.generate([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=3)
        a.result(60)                    # 2 pages now registry-held
        b = eng.generate([9, 8, 7, 6, 5, 4, 3, 2], max_new_tokens=3)
        out = b.result(60)              # needs eviction to fit
        st = eng.stats()
    finally:
        eng.close()
    assert out == _greedy_reference(model, params,
                                    [9, 8, 7, 6, 5, 4, 3, 2], 3)
    assert st['counts']['page_evictions'] >= 1


def test_paged_bit_identity_with_flash_attention_knob():
    """MXNET_TPU_PALLAS=attention puts the prefill's attention on the
    flash kernel; the paged step itself is behind no knob (it gathers
    here, and walks the table on a TPU: ``paged.walks_pages``). Token
    streams stay bit-identical to the knob-off paged path and the
    reference, and the knob splits the compiled-program keys (no
    latching)."""
    import mxnet_tpu as mx
    model, params = _model(max_len=48)
    requests = [([7, 2, 9], 5), ([1, 2, 3, 4, 5], 5)]
    off_prog = PagedDecodeProgram(model, params, slots=2,
                                  prefill_buckets=(4, 8), page_size=8)
    off_outs, _ = _run_engine(off_prog, requests)
    mx.config.set('MXNET_TPU_PALLAS', 'attention')
    try:
        on_prog = PagedDecodeProgram(model, params, slots=2,
                                     prefill_buckets=(4, 8),
                                     page_size=8)
        on_outs, _ = _run_engine(on_prog, requests)
        assert any(k.endswith(':pallas-attention')
                   for k in on_prog.trace_counts)
    finally:
        mx.config.unset('MXNET_TPU_PALLAS')
    assert on_outs == off_outs
    for (prompt, n), out in zip(requests, on_outs):
        assert out == _greedy_reference(model, params, prompt,
                                        len(out))


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

def test_spec_decoding_with_self_draft_accepts_everything():
    """Draft == target weights: every proposal matches the target's
    greedy token up to float32 verify precision — acceptance ~1 and
    the stream equals the non-speculative greedy stream."""
    model, params = _model(max_len=64)
    target = PagedDecodeProgram(model, params, slots=2,
                                prefill_buckets=(4, 8), page_size=8,
                                spec_k=2)
    draft = DecodeProgram(model, params, slots=2,
                          prefill_buckets=(4, 8))
    requests = [([7, 2, 9], 8), ([1, 2, 3, 4, 5], 8)]
    plain = PagedDecodeProgram(model, params, slots=2,
                               prefill_buckets=(4, 8), page_size=8)
    want, _ = _run_engine(plain, requests)
    outs, st = _run_engine(target, requests, draft=draft)
    assert outs == want
    assert st['spec']['proposed'] > 0
    assert st['spec']['acceptance_rate'] >= 0.9
    # speculation batches multiple tokens per verify: fewer device
    # rounds than tokens
    assert st['counts']['steps'] < sum(len(o) for o in outs)


def test_spec_decoding_small_draft_correct_and_counted():
    model, params = _model(max_len=64)
    dmodel, dparams = init_transformer_lm(vocab=23, units=16,
                                          hidden=16, layers=1,
                                          heads=2, max_len=64, seed=5)
    target = PagedDecodeProgram(model, params, slots=2,
                                prefill_buckets=(4, 8), page_size=8,
                                spec_k=3)
    draft = DecodeProgram(dmodel, dparams, slots=2,
                          prefill_buckets=(4, 8))
    requests = [([7, 2, 9], 8), ([4, 4, 2, 1], 8)]
    outs, st = _run_engine(target, requests, draft=draft)
    # greedy-to-float32-precision contract (docs/DIVERGENCES.md): on
    # this toy model the argmax margins are wide, so the stream equals
    # the exact greedy reference
    for (prompt, n), out in zip(requests, outs):
        assert out == _greedy_reference(model, params, prompt,
                                        len(out))
    assert st['spec']['k'] == 3
    assert st['spec']['proposed'] > 0
    assert 0.0 <= st['spec']['acceptance_rate'] <= 1.0


def test_spec_draft_cache_has_no_holes_after_full_acceptance():
    """A fully-accepted round advances pos past the last proposal's
    position; the draft must still have written that row (the engine
    feeds the final proposal to the draft even though its output is
    discarded) — otherwise every later round attends a zero-row hole
    and acceptance silently decays."""
    model, params = _model(max_len=64)
    target = PagedDecodeProgram(model, params, slots=1,
                                prefill_buckets=(4,), page_size=8,
                                spec_k=2)
    draft = DecodeProgram(model, params, slots=1,
                          prefill_buckets=(4,))
    eng = DecodeEngine(target, timeout_s=60.0, draft=draft)
    try:
        s = eng.generate([7, 2, 9], max_new_tokens=12)
        out = s.result(60)
        st = eng.stats()
        # self-draft: every round fully accepts
        assert st['spec']['acceptance_rate'] == 1.0
        # every draft KV row the sequence consumed is non-zero (the
        # transformer's K projection of a real token is never all-0)
        k0 = np.asarray(eng._draft_cache['l0_k'])[0]   # (max_len, U)
        final_pos = 3 + len(out)
        for pos in range(final_pos - 1):
            assert np.abs(k0[pos]).sum() > 0, \
                'draft KV hole at position %d' % pos
    finally:
        eng.close()
    assert out == _greedy_reference(model, params, [7, 2, 9], 12)


def test_spec_stream_length_parity_at_max_len_wall():
    """Near max_len the speculative stream must emit exactly the
    tokens the plain greedy path emits — the per-token length check
    uses each token's own position, not the chunk-advanced one (which
    would truncate already-verified tokens)."""
    model, params = init_transformer_lm(vocab=23, units=16, hidden=24,
                                        layers=2, heads=4, max_len=16)
    plain = PagedDecodeProgram(model, params, slots=1,
                               prefill_buckets=(4,), page_size=8)
    want, _ = _run_engine(plain, [([7, 2, 9], 50)])
    target = PagedDecodeProgram(model, params, slots=1,
                                prefill_buckets=(4,), page_size=8,
                                spec_k=2)
    draft = DecodeProgram(model, params, slots=1, prefill_buckets=(4,))
    got, _ = _run_engine(target, [([7, 2, 9], 50)], draft=draft)
    assert got == want
    assert len(got[0]) == 16 - 3        # filled to the wall


def test_spec_requires_paged_target_and_matching_slots():
    model, params = _model()
    draft = DecodeProgram(model, params, slots=2,
                          prefill_buckets=(4,))
    slot_prog = DecodeProgram(model, params, slots=2,
                              prefill_buckets=(4,))
    with pytest.raises(ValueError):
        DecodeEngine(slot_prog, draft=draft)
    paged_k0 = PagedDecodeProgram(model, params, slots=2,
                                  prefill_buckets=(4,), page_size=8,
                                  spec_k=0)
    with pytest.raises(ValueError):
        DecodeEngine(paged_k0, draft=draft)
    paged = PagedDecodeProgram(model, params, slots=3,
                               prefill_buckets=(4,), page_size=8,
                               spec_k=2)
    with pytest.raises(ValueError):
        DecodeEngine(paged, draft=draft)     # slots mismatch
    rnn_model, rnn_params = init_rnn_lm(vocab=23, embed=8, hidden=12,
                                        layers=1, mode='lstm',
                                        max_len=32)
    rnn_draft = DecodeProgram(rnn_model, rnn_params, slots=2,
                              prefill_buckets=(4,))
    paged2 = PagedDecodeProgram(model, params, slots=2,
                                prefill_buckets=(4,), page_size=8,
                                spec_k=2)
    with pytest.raises(ValueError):
        DecodeEngine(paged2, draft=rnn_draft)   # no positional cache
    # a PAGED draft is rejected typed too: the engine drives the
    # draft with slot-cache signatures (freeze drafts paged=False)
    paged_draft = PagedDecodeProgram(model, params, slots=2,
                                     prefill_buckets=(4,),
                                     page_size=8)
    with pytest.raises(ValueError):
        DecodeEngine(paged2, draft=paged_draft)


def test_spec_draft_stays_in_lockstep_through_prefix_extension():
    """A prefix-hit sequence streams its suffix through plain paged
    ticks before speculation resumes; those ticks must advance the
    DRAFT cache too, or later proposals attend holes. With
    draft == target weights the post-extension stream must stay exact
    with high acceptance."""
    model, params = _model(max_len=64)
    target = PagedDecodeProgram(model, params, slots=2,
                                prefill_buckets=(8,), page_size=8,
                                spec_k=2)
    draft = DecodeProgram(model, params, slots=2,
                          prefill_buckets=(8,))
    base = [3, 1, 4, 1, 5, 9]           # partial page: hits extend
    ref = _greedy_reference(model, params, base, 8)
    eng = DecodeEngine(target, timeout_s=60.0, draft=draft)
    try:
        # register the prefix without writing into the tail
        # (max_new=1: the registration survives for B to hit)
        a = eng.generate(base, max_new_tokens=1)
        a.result(60)
        b = eng.generate(base, max_new_tokens=8)    # prefix hit
        assert b.result(60) == ref
        st = eng.stats()
    finally:
        eng.close()
    assert st['counts']['prefix_hits'] >= 1
    assert st['spec']['proposed'] > 0
    assert st['spec']['acceptance_rate'] >= 0.9


# ---------------------------------------------------------------------------
# accounting + status
# ---------------------------------------------------------------------------

def test_pool_bytes_accounting_and_per_sequence_amortized():
    model, params = _model(max_len=48)
    prog = PagedDecodeProgram(model, params, slots=4,
                              prefill_buckets=(8,), page_size=8,
                              pages=13)
    # pool = pages x ps x units x 4 B x (2 entries x layers)
    assert prog.cache_bytes() == 13 * 8 * 16 * 4 * 2 * 2
    assert prog.page_bytes() == 8 * 16 * 4 * 2 * 2
    # a 12-token sequence holds 2 pages, not max_len rows
    assert prog.per_sequence_bytes(12) == 2 * prog.page_bytes()
    assert prog.per_sequence_bytes() == 6 * prog.page_bytes()
    slot = DecodeProgram(model, params, slots=4, prefill_buckets=(8,))
    # the satellite fix: pool bytes report REAL residency, not the
    # slots x max_len worst case
    assert prog.cache_bytes() < slot.cache_bytes()


def test_engine_cache_accounting_and_status_block():
    model, params = _model(max_len=48)
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(8,), page_size=8)
    with serving.InferenceSession(prog, watchdog=False) as sess:
        sess.generate([5, 3, 1], max_new_tokens=3).result(30)
        st = sess.status()
    assert st['paged']['page_size'] == 8
    assert st['paged']['max_pages'] == 6
    acct = st['decode']['cache']
    assert acct['paged'] is True
    assert acct['cache_bytes'] == prog.cache_bytes()
    assert acct['per_sequence_bytes_amortized'] >= prog.page_bytes()
    assert acct['max_concurrent_sequences_per_gb'] > 0
    assert st['decode']['pages']['pages_total'] == prog.pages - 1


def test_degraded_fallback_rebuilds_pool_and_matches_tokens():
    """A transient device failure mid-paged-decode completes in-flight
    sequences degraded with the SAME tokens, resets the allocator +
    prefix registry with the pool, and the engine serves clean
    afterwards."""
    import mxnet_tpu as mx
    model, params = _model(max_len=48)
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(8,), page_size=8)
    ref = _greedy_reference(model, params, [1, 2, 3], 5)
    mx.config.set('MXNET_TPU_FAULT', 'device_loss@serving.decode:3')
    try:
        eng = DecodeEngine(prog, timeout_s=60.0)
        try:
            streams = [eng.generate([1, 2, 3], max_new_tokens=5)
                       for _ in range(3)]
            outs = [s.result(60) for s in streams]
            assert all(o == ref for o in outs)
            assert any(s.degraded for s in streams)
            mx.config.unset('MXNET_TPU_FAULT')
            # recovery: pool/registry rebuilt; clean serving resumes
            time.sleep(0.1)
            ok = eng.generate([1, 2, 3], max_new_tokens=5)
            assert ok.result(60) == ref
            st = eng.stats()
            assert st['free_slots'] == 2
        finally:
            eng.close()
    finally:
        mx.config.unset('MXNET_TPU_FAULT')


# ---------------------------------------------------------------------------
# the compiled programs' pool operations promise their indices
# ---------------------------------------------------------------------------

def _pool_ops(jaxpr):
    """(primitive, name stack, result shape, mode) of every equation
    that could touch a pool, sub-jaxprs included."""
    import jax
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ('gather', 'scatter', 'select_n',
                                  'dynamic_update_slice', 'reshape'):
            out.append((eqn.primitive.name,
                        str(eqn.source_info.name_stack),
                        tuple(eqn.outvars[0].aval.shape),
                        eqn.params.get('mode')))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _pool_ops(sub)
    return out


def _step_case(caller):
    """(the traced function's jaxpr, layers) of one caller of the pool
    operations, at toy sizes."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.decode import init_cohere2_moe_lm
    from mxnet_tpu.serving.decode.paged import (pool_avals,
                                                window_table_pages)
    slots, ps = 3, 4

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    if caller == 'Cohere2MoELM.paged_step':
        model, params = init_cohere2_moe_lm(seed=0)
        pool = pool_avals(model.paged_spec(ps), 33, 13)
        tables = {'full': i32(slots, model.max_len // ps),
                  'window': i32(slots, window_table_pages(
                      model.window, ps, model.max_len))}
        args = (i32(slots), i32(slots), tables)
        fn, layers = model.paged_step, len(model.layer_types)
    else:
        model, params = _model(max_len=32)
        pool = pool_avals(model.paged_spec(ps), 25)
        tokens = i32(slots, 3) if caller.endswith('verify') \
            else i32(slots)
        args = (tokens, i32(slots), i32(slots, 32 // ps))
        fn = getattr(model, caller.split('.')[1])
        layers = model.layers
    return jax.make_jaxpr(fn)(params, pool, *args), pool, layers


@pytest.mark.parametrize('caller', ['TransformerLM.paged_step',
                                    'TransformerLM.paged_verify',
                                    'Cohere2MoELM.paged_step'])
def test_pool_operations_promise_their_indices(caller):
    """Every family reads its K/V view through ``paged.gather_pages``
    and appends through ``paged.scatter_rows``, both on indices the
    engine guarantees: no fill is laid over the gathered view (GPT-1's
    step spent 57 % of its time in one, PERF.md PR 31) and no append is
    unrolled into a dynamic_update_slice a slot."""
    from jax.lax import GatherScatterMode
    jaxpr, pool, layers = _step_case(caller)
    ops = _pool_ops(jaxpr.jaxpr)
    pools = {tuple(a.shape) for a in pool.values()}
    views = [(shape, mode) for prim, scope, shape, mode in ops
             if prim == 'gather' and 'kv_gather' in scope]
    assert len(views) == 2 * layers
    assert all(mode == GatherScatterMode.PROMISE_IN_BOUNDS
               for _, mode in views)
    # the view as gathered (slots, pages, page_size, row) and as the
    # attention takes it (slots, pages * page_size, row)
    filled = {shape for shape, _ in views} | {
        (s[0], s[1] * s[2]) + s[3:] for s, _ in views}
    assert not [op for op in ops
                if op[0] == 'select_n' and op[2] in filled]
    appends = [mode for prim, _, shape, mode in ops
               if prim == 'scatter' and shape in pools]
    assert len(appends) == 2 * layers
    assert all(mode == GatherScatterMode.PROMISE_IN_BOUNDS
               for mode in appends)
    assert not [op for op in ops
                if op[0] == 'dynamic_update_slice' and op[2] in pools]
    if caller.startswith('TransformerLM'):
        # nor is a head split out of the view (_attend_view)
        assert not [op for op in ops if op[0] == 'reshape' and any(
            len(op[2]) == 4 and op[2][:2] == v[:2] for v in filled
            if len(v) == 3)]


@pytest.mark.parametrize('queries', [1, 3])
def test_attention_over_the_view_equals_attention_head_by_head(queries):
    """``_attend_view`` splits no head out of the (slots, L, units) view
    (on a TPU the split is a relayout of the whole view); what it
    computes is each head's softmax attention over its own columns."""
    import jax.numpy as jnp
    model, _ = _model(max_len=32)
    rs = np.random.RandomState(queries)
    s, length, u, h = 3, 20, model.units, model.heads
    q = rs.randn(s, queries, u).astype('float32')
    keys = rs.randn(s, length, u).astype('float32')
    values = rs.randn(s, length, u).astype('float32')
    seen = rs.randint(1, length, (s, queries))
    bias = np.where(np.arange(length)[None, None] <= seen[:, :, None],
                    0.0, -1e9).astype('float32')
    got = np.asarray(model._attend_view(
        jnp.asarray(q), jnp.asarray(keys), jnp.asarray(values),
        jnp.asarray(bias)))
    d = u // h
    want = np.zeros((s, queries, u))
    for head in range(h):
        cols = slice(head * d, (head + 1) * d)
        scores = np.einsum('scd,sld->scl', q[..., cols].astype('float64'),
                           keys[..., cols]) + bias
        att = np.exp(scores - scores.max(-1, keepdims=True))
        att /= att.sum(-1, keepdims=True)
        want[..., cols] = np.einsum('scl,sld->scd', att, values[..., cols])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    rows = np.asarray(model._attend_rows(
        jnp.asarray(q[:, 0]), jnp.asarray(keys), jnp.asarray(values),
        jnp.asarray(bias[:, :1])))
    np.testing.assert_allclose(rows, want[:, 0], rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the one-token step walks the table on a TPU, gathers anywhere else;
# the counters that say how much of the view was live
# ---------------------------------------------------------------------------

def test_kv_pages_walked_and_view_follow_the_positions():
    """``kv_pages_walked``: over a run's steps, live slots and attention
    layers, ``position // page_size + 1``; ``kv_pages_view``: ``slots x
    max_pages x layers`` a step. Booked by the engine from the positions
    it holds (``PageOwner.step_pages``), no device read."""
    model, params = _model(max_len=48)
    ps, slots = 8, 3
    prog = PagedDecodeProgram(model, params, slots=slots,
                              prefill_buckets=(4, 8, 16), page_size=ps)
    requests = [([7, 2, 9], 9), ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], 6)]
    outs, stats = _run_engine(prog, requests, prefix_cache=False)
    counts = stats['counts']
    # a request of n new tokens: one from its prefill, then a step at
    # every position from the prompt's length on
    walked = sum(model.layers * (pos // ps + 1)
                 for (prompt, _n), out in zip(requests, outs)
                 for pos in range(len(prompt), len(prompt) + len(out) - 1))
    assert counts['kv_pages_walked'] == walked
    assert counts['kv_pages_view'] == \
        counts['steps'] * slots * (48 // ps) * model.layers
    assert 0 < counts['kv_pages_walked'] < counts['kv_pages_view']
    assert counts['kv_page_copies'] == 0      # the CPU rig's step gathers


def test_kv_page_copies_are_the_copies_the_kernels_flags_give(monkeypatch):
    """``PageOwner.step_copies`` counts the copies of a step's walks by
    the kernel's own rule (``ops.pallas.attention.walk_copy_runs``: a
    chunk of consecutive pages is one copy): hand-made tables through
    the owner's numpy and through the kernel's ``jax.numpy`` give one
    count, a layer; nothing where no layer walks."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.attention import (_RUN_PAGES,
                                                walk_block_pages,
                                                walk_copy_runs)
    from mxnet_tpu.serving.decode import paged
    from mxnet_tpu.serving.decode.paged import PageOwner
    layers, ps, width = 3, 16, 768
    spec = PagedCacheSpec(
        {'l%d_%s' % (i, kv): ((width,), 'float32')
         for i in range(layers) for kv in 'kv'}, ps, 64 * ps)

    def owner(spec=spec, pools={'full': 400}):
        return PageOwner(spec, pools, threading.Lock(), False, {})

    gathers = owner()
    monkeypatch.setattr(paged, 'walks_pages', lambda shape, dtype: True)
    walks = owner()
    block = walk_block_pages(2, ps, width, 'float32')
    assert block == 16 and _RUN_PAGES == 8
    rows = [list(range(1, 41)),                   # one run: 5 chunks
            list(range(100, 108)) + [300, 120, 121, 122, 123],
            [],                                   # an empty slot
            list(range(90, 70, -1)),              # descending: 20 pages
            [7], []]
    positions = np.asarray([40 * ps - 1, 13 * ps - 5, 0, 20 * ps - 16, 3,
                            0], 'int32')
    tables = np.full((6, 64), TRASH_PAGE, 'int32')
    for slot, row in enumerate(rows):
        tables[slot, :len(row)] = row
    assert walks.step_copies(tables, positions) \
        == layers * (5 + (1 + 5) + 20 + 1)
    # the kernel's flags for the step's operands: the same rule, traced
    run, copies = walk_copy_runs(jnp, jnp.asarray(tables),
                                 jnp.asarray(positions), ps, block,
                                 TRASH_PAGE)
    assert [int(x) for x in copies] == [5, 6, 0, 20, 1, 0]
    assert [int(x) for x in run.sum(-1)] == [5, 1, 0, 0, 0, 0]
    # where the step gathers, nothing: the CPU rig, and a cache with a
    # ring on a TPU too (cohere2.py gathers its full layers as well)
    assert gathers.step_copies(tables, positions) == 0
    ring = PagedCacheSpec(
        {'l0_k': ((width,), 'float32'), 'l0_v': ((width,), 'float32'),
         'l1_k': ((width,), 'float32'), 'l1_v': ((width,), 'float32')},
        ps, 64 * ps, window=32, window_entries=('l0_k', 'l0_v'))
    assert owner(ring, {'full': 40, 'window': 20}).step_copies(
        {'full': tables, 'window': tables[:, :4]}, positions) == 0


def test_step_pages_counts_a_ring_no_further_than_its_columns():
    from mxnet_tpu.serving.decode.paged import PageOwner
    spec = PagedCacheSpec(
        {'l0_k': ((8,), 'float32'), 'l0_v': ((8,), 'float32'),
         'l1_k': ((8,), 'float32'), 'l1_v': ((8,), 'float32'),
         'l2_k': ((8,), 'float32'), 'l2_v': ((8,), 'float32')},
        4, 64, window=8, window_entries=('l0_k', 'l0_v', 'l1_k', 'l1_v'))
    import threading
    owner = PageOwner(spec, {'full': 40, 'window': 20}, threading.Lock(),
                      False, {})
    ring = spec.window_pages
    walked, view = owner.step_pages(5, [0, 9, 63])
    # one full layer: 1 + 3 + 16 pages; two window layers: the ring's
    # columns at most
    assert walked == (1 + 3 + 16) + 2 * (1 + min(3, ring) + ring)
    assert view == 5 * (16 + 2 * ring)


def test_the_step_traced_for_the_cpu_gathers_and_calls_no_kernel():
    """The walk's choice is made by where the computation is placed
    (``paged.walks_pages``): on the CPU rig the step holds the gather
    and no ``pallas_call``, whatever the pool's geometry."""
    import jax
    from mxnet_tpu.serving.decode import TransformerLM
    from mxnet_tpu.serving.decode.paged import pool_avals, walks_pages
    # GPT-1's pool geometry, which Mosaic takes: placement alone decides
    model = TransformerLM(dict(vocab=64, units=768, hidden=64, layers=1,
                               heads=12, max_len=64))
    pool = pool_avals(model.paged_spec(16), 9)
    assert not walks_pages(pool['l0_k'].shape, pool['l0_k'].dtype)
    params = jax.eval_shape(lambda: model.init_params(0))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, 'int32')

    jaxpr = jax.make_jaxpr(model.paged_step)(
        params, pool, i32(2), i32(2), i32(2, 4))
    prims = [op[0] for op in _pool_ops(jaxpr.jaxpr)]
    assert prims.count('gather') >= 2
    assert 'pallas_call' not in str(jaxpr)
    # the verify's chunk gathers wherever it is placed
    import inspect
    assert 'walks_pages' not in inspect.getsource(model.paged_verify)
