"""Decode family ``cohere2_moe``: a parallel attention + mixture-of-experts
block over two kinds of layer.

One block, input ``x`` (hidden wide), layer ``l``::

    n      = LayerNorm(x) * g_l                     (no bias, one norm
                                                     feeds both branches)
    q,k,v  = n Wq, n Wk, n Wv                       grouped heads: query
                                                     head h reads KV head
                                                     h // (heads/kv_heads)
    sliding layer: interleaved RoPE on q and k (pairs (2i, 2i+1)), key j
                   visible to query t iff j <= t and t - j < window
    full layer:    no positional term, key j visible iff j <= t
    attn   = softmax(q k^T / sqrt(d) + mask) v Wo
    s      = sigmoid(n Wr) over ALL experts; T = the top_k largest;
             w_e = s_e / sum_{e in T} s_e
    routed = sum_{e in T, e held here} w_e W2_e(silu(W1_e n) * W3_e n)
    shared = mean_j S2_j(silu(S1_j n) * S3_j n)
    y      = x + attn + routed + shared
    logits = logit_scale * LayerNorm_f(y) E^T      (tied embedding)

**The chip's share.** The model is told which experts it holds
(``held_experts``), how many query and KV heads (``heads``,
``kv_heads``) and how many rows of the vocabulary. It routes over all
``experts`` scores, normalises over all ``top_k`` selected whether they
are held or not, and adds only what its own experts give. Nothing stands
in for the absent chips.

**The expert layer** and the attention over a whole sequence and
over gathered rows are ``blocks.py``'s, shared with the other family
that holds a share of its experts: this module routes (sigmoid
scores, weights normalised over the selected) and hands the layer
its gate weights.

**Precision.** Parameters and cache in ``dtype`` (bfloat16 as served),
matrix products with ``dtype`` operands and float32 accumulation;
LayerNorm, softmax, RoPE and the residual stream in float32. The router's
product and its sigmoid are float32 at the highest matmul precision: a
selection that flips is a discontinuity, not a rounding.

Implemented: ``full_forward``, ``paged_prefill``, ``paged_step``. The
slot cache (``cache_spec`` / ``prefill`` / ``step``), ``paged_verify``
and ``lora_targets`` raise :class:`FamilyUnsupported`.
"""
from __future__ import annotations

import numpy as onp

from . import blocks
from .model import _FAMILIES, DecodeModel, FamilyUnsupported
from .paged import (PagedCacheSpec, gather_pages, ring_key_positions,
                    scatter_pages, scatter_rows)

__all__ = ['Cohere2MoELM', 'init_cohere2_moe_lm']

SLIDING, FULL = 'sliding_attention', 'full_attention'


class Cohere2MoELM(DecodeModel):
    """config: vocab, max_len, hidden, head_dim, heads, kv_heads (both
    as held here), layer_types (``sliding_attention`` /
    ``full_attention`` per layer), window, rope_theta, eps, experts
    (the router's width), held_experts (ids held here), top_k,
    shared_experts, expert_hidden, logit_scale, dtype; optional
    ``prefill_block`` (queries a block of prefill attention, 512).

    params: embed (V, H), lnf_g (H,), and per layer ``l{i}_``: ln_g
    (H,), q_w (H, heads*d), k_w / v_w (H, kv_heads*d), o_w (heads*d,
    H), router_w (H, experts), w1 / w3 (held, H, F), w2 (held, F, H),
    s1 / s3 (shared, H, F), s2 (shared, F, H).
    """

    family = 'cohere2_moe'
    supports_paging = True
    # device-side counts a step returns beside its logits; the program
    # appends them to the tokens it reads back (one transfer a tick)
    step_stats = ('moe_assignments', 'moe_assignments_here',
                  'moe_expert_load_max')

    def __init__(self, config):
        config = dict(config)
        config.setdefault('logit_scale', 1.0)
        config.setdefault('dtype', 'bfloat16')
        config.setdefault('prefill_block', 512)
        config['layer_types'] = list(config['layer_types'])
        config['held_experts'] = [int(e) for e in config['held_experts']]
        super().__init__(config)
        self.hidden = int(config['hidden'])
        self.head_dim = int(config['head_dim'])
        self.heads = int(config['heads'])
        self.kv_heads = int(config['kv_heads'])
        self.layer_types = config['layer_types']
        self.layers = len(self.layer_types)
        self.window = int(config['window'])
        self.rope_theta = float(config['rope_theta'])
        self.eps = float(config['eps'])
        self.experts = int(config['experts'])
        self.held = config['held_experts']
        self.top_k = int(config['top_k'])
        self.shared = int(config['shared_experts'])
        self.expert_hidden = int(config['expert_hidden'])
        self.logit_scale = float(config['logit_scale'])
        self.dtype = str(config['dtype'])
        self.prefill_block = int(config['prefill_block'])
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError('unknown layer types %r' % sorted(bad))
        if self.heads % self.kv_heads:
            raise ValueError('heads %d not divisible by kv_heads %d'
                             % (self.heads, self.kv_heads))
        if self.head_dim % 2:
            raise ValueError('interleaved RoPE needs an even head_dim')
        self._experts = blocks.HeldExperts(
            self.experts, self.held, self.top_k, self.hidden, self.dtype)
        self._local_of = self._experts.local_of

    # -- what this family does not implement --------------------------------

    def cache_spec(self):
        raise FamilyUnsupported(
            self.family, 'the slot cache (cache_spec / prefill / step): '
            'a window layer keeps a ring of pages, which only the paged '
            'cache manager holds; freeze it paged')

    def prefill(self, params, cache, tokens, length, slot):
        self.cache_spec()

    def step(self, params, cache, tokens, positions):
        self.cache_spec()

    def paged_verify(self, params, pool, tokens, positions, tables,
                     ad=None):
        raise FamilyUnsupported(
            self.family, 'paged_verify (speculative decoding): a chunk '
            'that crosses a page boundary of a window ring would need '
            'two pages released and taken inside one call')

    def lora_targets(self):
        raise FamilyUnsupported(
            self.family, 'lora_targets (low-rank adapters): no adapter '
            'layout is defined for stacked expert weights')

    # -- block math ----------------------------------------------------------

    def _ln(self, x, g):
        import jax
        import jax.numpy as jnp
        x = x.astype('float32')
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + self.eps) \
            * g.astype('float32')

    def _mm(self, spec, a, b):
        """Matrix product with ``dtype`` operands, float32 result."""
        return blocks.mm(spec, a, b, self.dtype)

    def _rope(self, x, positions):
        """Interleaved rotary embedding over all of head_dim: pair
        (2i, 2i+1) turns by ``position * theta ** (-2i / d)``.
        ``x`` (..., heads, d), ``positions`` (...,)."""
        import jax.numpy as jnp
        half = self.head_dim // 2
        inv = self.rope_theta ** (
            -jnp.arange(half, dtype='float32') * 2.0 / self.head_dim)
        ang = positions.astype('float32')[..., None, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        pairs = x.astype('float32').reshape(x.shape[:-1] + (half, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape)

    def _qkv(self, p, n, positions, sliding):
        """Projections of layer input ``n`` (T, H): q (T, kv_heads,
        group, d) scaled, k and v (T, kv_heads, d), all in ``dtype``;
        RoPE on a sliding layer only."""
        t, d = n.shape[0], self.head_dim
        q = self._mm('th,ho->to', n, p('q_w')).reshape(t, self.heads, d)
        k = self._mm('th,ho->to', n, p('k_w')).reshape(t, self.kv_heads,
                                                      d)
        v = self._mm('th,ho->to', n, p('v_w')).reshape(t, self.kv_heads,
                                                      d)
        if sliding:
            q, k = self._rope(q, positions), self._rope(k, positions)
        q = (q * (1.0 / float(onp.sqrt(d)))).reshape(
            t, self.kv_heads, self.heads // self.kv_heads, d)
        return q.astype(self.dtype), k.astype(self.dtype), \
            v.astype(self.dtype)

    def _route(self, p, n):
        """Scores over all experts (float32, highest precision), the
        ``top_k`` largest and their weights normalised over all of
        them: (weights (T, K) float32, expert ids (T, K))."""
        import jax
        import jax.numpy as jnp
        logits = jnp.einsum('th,he->te', n.astype('float32'),
                            p('router_w').astype('float32'),
                            precision=jax.lax.Precision.HIGHEST)
        top_s, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), self.top_k)
        return top_s / jnp.sum(top_s, axis=-1, keepdims=True), top_i

    def _shared(self, p, n):
        import jax
        with jax.named_scope('shared'):
            return blocks.gated_ffn(
                'th,jhf->jtf', 'jtf,jfh->th', n, p('s1'), p('s3'),
                p('s2'), self.dtype) * (1.0 / self.shared)

    def _moe_dense(self, p, n, live):
        """The decode step's expert layer (``blocks.HeldExperts.dense``)
        under this family's router."""
        import jax
        with jax.named_scope('router'):
            w, top_i = self._route(p, n)
        return self._experts.dense(n, w, top_i, live, p('w1'), p('w3'),
                                   p('w2'))

    def _expert_block(self, s):
        return self._experts.block(s)

    def _moe_grouped(self, p, n, length):
        """A prefill's expert layer (``blocks.HeldExperts.grouped``)
        under this family's router."""
        import jax
        with jax.named_scope('router'):
            w, top_i = self._route(p, n)
        return self._experts.grouped(n, w, top_i, length, p('w1'),
                                     p('w3'), p('w2'))

    def _attend_blocks(self, q, k, v, sliding):
        return blocks.attend_blocks(q, k, v, self.prefill_block,
                                    self.window if sliding else None,
                                    self.dtype)

    def _attend_rows(self, q, keys, values, seen):
        return blocks.attend_rows(q, keys, values, seen, self.dtype)

    def _embed(self, params, tokens):
        import jax
        import jax.numpy as jnp
        with jax.named_scope('embed'):
            return jnp.take(params['embed'], tokens,
                            axis=0).astype('float32')

    def _head(self, params, x):
        import jax
        with jax.named_scope('lm_head'):
            return self.logit_scale * self._mm(
                '...h,vh->...v', self._ln(x, params['lnf_g']),
                params['embed'])

    def _sequence_pass(self, params, tokens, length):
        """One whole sequence, tokens (S,): the hidden states before
        the final norm (S, H) and each layer's (k, v) (S, kv_heads *
        d). Rows at or past ``length`` are padding: causal attention
        keeps them from every real row and the router sends them
        nowhere. The prefill AND the uncached reference pass."""
        import jax
        import jax.numpy as jnp
        s = tokens.shape[0]
        positions = jnp.arange(s)
        x = self._embed(params, tokens)
        kvs = []
        for i, kind in enumerate(self.layer_types):
            p = lambda name: params['l%d_%s' % (i, name)]  # noqa: E731
            with jax.named_scope('layer%d' % i):
                n = self._ln(x, p('ln_g'))
                with jax.named_scope('attn'):
                    q, k, v = self._qkv(p, n, positions, kind == SLIDING)
                    kvs.append((k.reshape(s, -1), v.reshape(s, -1)))
                    attn = self._mm(
                        'to,oh->th',
                        self._attend_blocks(q, k, v, kind == SLIDING),
                        p('o_w'))
                with jax.named_scope('moe'):
                    routed, _counts = self._moe_grouped(p, n, length)
                    x = x + attn + routed + self._shared(p, n)
        return x, kvs

    def full_forward(self, params, tokens):
        """tokens (B, T) -> logits (B, T, V), no cache."""
        import jax.numpy as jnp
        t = tokens.shape[1]
        return jnp.stack([
            self._head(params, self._sequence_pass(params, row, t)[0])
            for row in tokens])

    # -- paged cache paths ---------------------------------------------------

    def paged_spec(self, page_size):
        """One (pages, page_size, kv_heads * d) pool per layer K and V;
        a sliding layer's entries are window entries (their pools and
        tables are sized by the window, paged.PagedCacheSpec)."""
        row = (self.kv_heads * self.head_dim,)
        return PagedCacheSpec(
            {'l%d_%s' % (i, kv): (row, self.dtype)
             for i in range(self.layers) for kv in ('k', 'v')},
            page_size, self.max_len, window=self.window,
            window_entries=['l%d_%s' % (i, kv)
                            for i, kind in enumerate(self.layer_types)
                            if kind == SLIDING for kv in ('k', 'v')])

    @staticmethod
    def _by_kind(arg):
        """A program with both kinds of layer hands page ids and tables
        over as ``{'full': ..., 'window': ...}``; with one kind, as the
        array alone."""
        return arg if isinstance(arg, dict) else {'full': arg,
                                                  'window': arg}

    def paged_prefill(self, params, pool, tokens, length, page_ids,
                      ad=None):
        """Prefill through the page tables: tokens (1, S); the K and V
        of a full layer land in ``page_ids['full']``, a sliding
        layer's in ``page_ids['window']``, which names the trash page
        for every page already behind the window. Returns (pool',
        logits (V,) at position ``length - 1``)."""
        import jax.numpy as jnp
        from jax import lax
        del ad
        ids = self._by_kind(page_ids)
        s = tokens.shape[1]
        x, kvs = self._sequence_pass(params, tokens[0], length)
        ps = pool['l0_k'].shape[1]
        pad = ids['full'].shape[0] * ps - s
        pool = dict(pool)
        for i, kind in enumerate(self.layer_types):
            to = ids['window' if kind == SLIDING else 'full']
            for name, arr in zip('kv', kvs[i]):
                key = 'l%d_%s' % (i, name)
                pool[key] = scatter_pages(
                    pool[key], jnp.pad(arr, ((0, pad), (0, 0))), to)
        last = lax.dynamic_slice_in_dim(x, length - 1, 1, 0)[0]
        return pool, self._head(params, last)

    def paged_step(self, params, pool, tokens, positions, tables,
                   ad=None):
        """One decode step over the page pools: tokens / positions
        (slots,). A full layer gathers ``tables['full']`` (slots,
        max_pages); a sliding layer gathers only the ring
        ``tables['window']`` (slots, window_pages) and masks by each
        row's own position (paged.ring_key_positions). A slot is live
        iff its position is above 0 (a sequence's first step comes
        after at least one prompt token). Returns (pool', logits
        (slots, V), counts (3,) int32 in ``step_stats``' order: live
        slots x top_k x layers, the assignments among them that landed
        on a held expert, and the largest count one held expert of one
        layer saw)."""
        import jax
        import jax.numpy as jnp
        del ad
        tabs = self._by_kind(tables)
        ps = pool['l0_k'].shape[1]
        pos = positions[:, None]
        live = positions > 0
        full_seen = jnp.arange(tabs['full'].shape[1] * ps)[None] <= pos
        ring = tabs['window'].shape[1]
        kpos = ring_key_positions(positions, ring, ps)
        ring_seen = (kpos >= 0) & (kpos <= pos) & (pos - kpos
                                                   < self.window)
        page = positions // ps
        at = {'full': jnp.take_along_axis(
                  tabs['full'], page[:, None], axis=1)[:, 0],
              'window': jnp.take_along_axis(
                  tabs['window'], (page % ring)[:, None], axis=1)[:, 0]}
        offsets = positions % ps
        x = self._embed(params, tokens)
        pool = dict(pool)
        here = jnp.zeros((), 'int32')
        load = jnp.zeros((), 'int32')
        for i, kind in enumerate(self.layer_types):
            p = lambda name: params['l%d_%s' % (i, name)]  # noqa: E731
            sliding = kind == SLIDING
            group = 'window' if sliding else 'full'
            kk, vk = 'l%d_k' % i, 'l%d_v' % i
            with jax.named_scope('layer%d' % i):
                n = self._ln(x, p('ln_g'))
                with jax.named_scope('attn'):
                    q, k, v = self._qkv(p, n, positions, sliding)
                    pool[kk] = scatter_rows(
                        pool[kk], k.reshape(k.shape[0], -1), at[group],
                        offsets)
                    pool[vk] = scatter_rows(
                        pool[vk], v.reshape(v.shape[0], -1), at[group],
                        offsets)
                keys = gather_pages(pool[kk], tabs[group])
                values = gather_pages(pool[vk], tabs[group])
                with jax.named_scope('attn'):
                    attn = self._mm(
                        'to,oh->th',
                        self._attend_rows(q, keys, values,
                                          ring_seen if sliding
                                          else full_seen),
                        p('o_w'))
                with jax.named_scope('moe'):
                    routed, counts = self._moe_dense(p, n, live)
                    x = x + attn + routed + self._shared(p, n)
                here = here + jnp.sum(counts)
                load = jnp.maximum(load, jnp.max(counts))
        routed = jnp.sum(live).astype('int32') * (self.top_k * self.layers)
        return pool, self._head(params, x), jnp.stack([routed, here, load])

    # -- construction --------------------------------------------------------

    def param_shapes(self):
        h, d, f = self.hidden, self.head_dim, self.expert_hidden
        eh, ns = len(self.held), self.shared
        shapes = {'embed': (self.vocab, h), 'lnf_g': (h,)}
        for i in range(self.layers):
            shapes.update({
                'l%d_ln_g' % i: (h,),
                'l%d_q_w' % i: (h, self.heads * d),
                'l%d_k_w' % i: (h, self.kv_heads * d),
                'l%d_v_w' % i: (h, self.kv_heads * d),
                'l%d_o_w' % i: (self.heads * d, h),
                'l%d_router_w' % i: (h, self.experts),
                'l%d_w1' % i: (eh, h, f), 'l%d_w3' % i: (eh, h, f),
                'l%d_w2' % i: (eh, f, h),
                'l%d_s1' % i: (ns, h, f), 'l%d_s3' % i: (ns, h, f),
                'l%d_s2' % i: (ns, f, h)})
        return shapes

    def init_params(self, seed=0):
        """Seeded normal leaves at 1/sqrt(fan-in) (the embedding at
        1), gains at 1, in ``dtype`` (tests; the benchmark makes its
        own)."""
        import jax.numpy as jnp
        rs = onp.random.RandomState(seed)
        out = {}
        for name, shape in self.param_shapes().items():
            if name.endswith('_g'):
                out[name] = jnp.ones(shape, self.dtype)
            elif name == 'embed':
                out[name] = jnp.asarray(rs.randn(*shape), self.dtype)
            else:
                fan_in = shape[-2] if len(shape) > 1 else shape[0]
                out[name] = jnp.asarray(
                    rs.randn(*shape) / onp.sqrt(fan_in), self.dtype)
        return out


_FAMILIES[Cohere2MoELM.family] = Cohere2MoELM


def init_cohere2_moe_lm(seed=0, **config):
    """Deterministic small model of the family: (model, params)."""
    small = dict(vocab=96, max_len=64, hidden=64, head_dim=16, heads=8,
                 kv_heads=2, layer_types=[SLIDING] * 3 + [FULL],
                 window=8, rope_theta=50000.0, eps=1e-5, experts=8,
                 held_experts=list(range(8)), top_k=2, shared_experts=2,
                 expert_hidden=32, dtype='float32')
    small.update(config)
    model = Cohere2MoELM(small)
    return model, model.init_params(seed)
