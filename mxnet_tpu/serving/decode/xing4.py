"""Decode family ``xing4_0``: latent attention (MLA) over a paged cache
of latent rows, a residual path of several streams mixed through
manifold-constrained hyper-connections, leading dense layers and then
layers of sigmoid-routed experts with a selection bias and a shared
expert, YaRN positions.

``X`` (n, C) is one token's float32 residual streams, ``n`` =
``hc_mult``::

    X_0    = E[token] repeated n times
    sublayer F (attention, then MLP or experts; each its own phi, a, b):
      r      = RMSNorm(vec(X))                  over all n*C values
      Hpre   = sigmoid(a_pre * (r phi_pre) + b_pre)              (n,)
      Hpost  = 2 * sigmoid(a_post * (r phi_post) + b_post)       (n,)
      M      = exp(clip(a_res * mat(r phi_res) + b_res, lo, hi)) (n, n)
      Hres   = hc_iters times: M <- M / (colsum(M) + hc_eps);
                               M <- M / (rowsum(M) + hc_eps)
      X'     = Hres X + outer(Hpost, F(RMSNorm_in(Hpre X)))
    attention F, input h (C), position t:
      cq = RMSNorm(h Wqa);  [q_nope | q_pe]_i = cq Wqb     heads i
      [c | k_pe] = h Wkva;  c = RMSNorm(c)
      the cache row of t is [c | rope_t(k_pe) | zeros]     in ``dtype``
      prefill: [k_nope | v]_i = c Wkvb;
        s_i(t,u) = g * (q_nope_i(t).k_nope_i(u) + rope_t(q_pe_i).rope_u(k_pe))
      step:    q'_i = q_nope_i Wkvb_K,i^T  (as wide as c);
        s_i(t,u) = g * (q'_i . c_u + rope_t(q_pe_i) . rope_u(k_pe))
        ctx_i = softmax_u(s_i) c_u;   v-part_i = ctx_i Wkvb_V,i
      out = concat_i(softmax_u(s_i) v_i) Wo
      g = yarn(mscale_all_dim)^2 / sqrt(nope + rope),
          yarn(m) = 0.1 * m * ln(factor) + 1
    dense MLP (the first ``dense_layers``): D2(silu(D1 h) * D3 h)
    experts: s = sigmoid(h Wr) (float32);  T = the top_k largest of
      s + bias;  g_e = routed_scale * s_e / sum_{f in T} s_f
      y = sum_{e in T, e held here} g_e * expert_e(h) + shared(h)
    logits = RMSNorm_f(sum of the n streams of X_last) W_head

**Two attention paths over one cache.** A prefill up-projects the
latent rows to heads and attends in blocks (``blocks.attend_blocks``, no
(S, S) tensor). The step absorbs ``Wkvb`` into the query and the output
and attends the latent rows themselves: one row serves every head, so a
step reads ``kv_rank + rope`` columns a cached token and layer, not
``heads x (nope + rope + v)``. Placed on a TPU one kernel walks the page
table (``paged.walks_pages``; values are the leading ``kv_rank`` columns
of the very page that holds the keys); anywhere else the table is
gathered. Which path runs is decided by what the program is (a prefill
or the step) and by where it is placed, by no knob.

**The cache.** One paged entry a layer, ``l<i>_c``: rows of
``row_width`` columns, ``kv_rank + rope`` rounded up to whole lanes of
128, the pad columns zero. Pages are registered and shared by prefix
like any full layer's.

**The chip's share**: told which experts it holds, it routes over all
``experts`` scores, normalises over all ``top_k`` selected, and adds
only what its own experts give (``blocks.HeldExperts``).

**The next-token module** (``mtp_logits``; built only where
``config['mtp']``): ``h' = [RMSNorm(x_t) ; RMSNorm(E[token_{t+1}])] Wm``,
one expert layer of this family on ``h'`` repeated ``n`` times, a final
norm of its own and the model's head. Nothing serves or drafts with it.

**Precision.** Parameters and cache rows in ``dtype`` (bfloat16 as
served), matrix products with ``dtype`` operands and float32
accumulation; the residual streams, every RMSNorm, softmax, the
hyper-connection coefficients with their Sinkhorn iterations, and the
router's product, sigmoid and sums in float32 at the highest precision.

Implemented: ``full_forward``, ``paged_prefill``, ``paged_step``,
``mtp_logits``; a sequence's latent rows migrate in the entry-keyed
``seqstate`` payload as any one page list does. The slot cache,
``paged_verify`` and ``lora_targets`` raise :class:`FamilyUnsupported`.
"""
from __future__ import annotations

import math
import re

import numpy as onp

from . import blocks
from .model import _FAMILIES, DecodeModel, FamilyUnsupported
from .paged import (PagedCacheSpec, gather_pages, scatter_pages,
                    scatter_rows, walks_pages)

__all__ = ['Xing4LM', 'init_xing4_lm', 'yarn_inv_freq']

# rows of the dense MLP a prefill multiplies at a time: its two products
# of 9216 columns in float32 are 75 KB a row
_MLP_ROWS = 2048
# tokens whose streams a prefill reads and writes at a time
_STREAM_ROWS = 2048
# tokens a prefill routes and takes through the held experts at a time
_MOE_ROWS = 4096


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """YaRN's frequencies for ``dim`` rotary columns (``dim // 2``
    values): per frequency a linear ramp between the interpolated
    (1 / factor) and the unscaled one, the ramp's ends where a
    frequency turns ``beta_fast`` and ``beta_slow`` times over
    ``original_max`` positions."""
    def turns_at(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (onp.arange(0, dim, 2, dtype='float64') / dim)
    ramp = onp.clip((onp.arange(dim // 2, dtype='float64') - low)
                    / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype('float32')


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


class Xing4LM(DecodeModel):
    """config: vocab, max_len, hidden, layers, dense_layers, eps, heads,
    q_rank, kv_rank, nope_dim, rope_dim, v_dim, dense_hidden, experts
    (the router's width), held_experts (ids held here), top_k,
    expert_hidden, shared_hidden, routed_scale, hc_mult, hc_iters,
    hc_eps, hc_clamp (lo, hi), rope_theta, yarn (factor, original_max,
    beta_fast, beta_slow, mscale, mscale_all_dim), dtype; optional
    ``prefill_block`` (queries a block of prefill attention, 512) and
    ``mtp`` (the next-token module's leaves exist, False).

    params: embed, head (V, C), lnf_g (C,), and per layer ``l{i}_``:
    ln1_g, ln2_g (C,), q_a (C, q_rank), q_a_g, q_b (q_rank, heads *
    (nope + rope)), kv_a (C, kv_rank + rope), kv_a_g (kv_rank,), kv_b
    (kv_rank, heads * (nope + v)), o_w (heads * v, C); two
    hyper-connections ``hc1_`` / ``hc2_``: g (n C,), phi (n C, 2 n + n
    n: pre | post | res), a (3,) and b (2 n + n n,) float32; a dense
    layer: d1 / d3 (C, D), d2 (D, C); an expert layer: router_w (C,
    experts), router_b (experts,) float32, w1 / w3 (held, C, F), w2
    (held, F, C), s1 / s3 (C, Fs), s2 (Fs, C). With ``mtp``: mtp_hnorm_g,
    mtp_enorm_g, mtp_lnf_g (C,), mtp_proj (2 C, C) and an expert
    layer's leaves under ``mtp_``.
    """

    family = 'xing4_0'
    supports_paging = True
    # device-side counts a step returns beside its logits
    step_stats = ('moe_assignments', 'moe_assignments_here',
                  'moe_expert_load_max')

    def __init__(self, config):
        config = dict(config)
        config.setdefault('dtype', 'bfloat16')
        config.setdefault('prefill_block', 512)
        config.setdefault('mtp', False)
        config['held_experts'] = [int(e) for e in config['held_experts']]
        config['yarn'] = dict(config['yarn'])
        config['hc_clamp'] = [float(v) for v in config['hc_clamp']]
        super().__init__(config)
        self.hidden = int(config['hidden'])
        self.layers = int(config['layers'])
        self.dense_layers = int(config['dense_layers'])
        self.eps = float(config['eps'])
        self.heads = int(config['heads'])
        self.q_rank = int(config['q_rank'])
        self.kv_rank = int(config['kv_rank'])
        self.nope = int(config['nope_dim'])
        self.rope = int(config['rope_dim'])
        self.v_dim = int(config['v_dim'])
        self.dense_hidden = int(config['dense_hidden'])
        self.experts = int(config['experts'])
        self.held = config['held_experts']
        self.top_k = int(config['top_k'])
        self.expert_hidden = int(config['expert_hidden'])
        self.shared_hidden = int(config['shared_hidden'])
        self.routed_scale = float(config['routed_scale'])
        self.n = int(config['hc_mult'])
        self.hc_iters = int(config['hc_iters'])
        self.hc_eps = float(config['hc_eps'])
        self.hc_lo, self.hc_hi = config['hc_clamp']
        self.dtype = str(config['dtype'])
        self.prefill_block = int(config['prefill_block'])
        self.mtp = bool(config['mtp'])
        if not 0 <= self.dense_layers <= self.layers:
            raise ValueError('dense_layers %d of %d layers'
                             % (self.dense_layers, self.layers))
        if self.rope % 2:
            raise ValueError('rope_dim %d is odd' % self.rope)
        yarn = config['yarn']
        self._inv_freq = yarn_inv_freq(
            self.rope, float(config['rope_theta']), float(yarn['factor']),
            int(yarn['original_max']), float(yarn['beta_fast']),
            float(yarn['beta_slow']))
        # what cos and sin are scaled by, and the score scale
        self._rope_scale = yarn_mscale(yarn['factor'], yarn['mscale']) \
            / yarn_mscale(yarn['factor'], yarn['mscale_all_dim'])
        self.score_scale = yarn_mscale(
            yarn['factor'], yarn['mscale_all_dim']) ** 2 \
            / math.sqrt(self.nope + self.rope)
        # a cache row: the latent, the roped key, zeros up to whole lanes
        self.row_width = -(-(self.kv_rank + self.rope) // 128) * 128
        self._experts = blocks.HeldExperts(
            self.experts, self.held, self.top_k, self.hidden, self.dtype)

    # -- what this family does not implement --------------------------------

    def cache_spec(self):
        raise FamilyUnsupported(
            self.family, 'the slot cache (cache_spec / prefill / step): '
            'its latent rows live in pages, which only the paged cache '
            'manager holds; freeze it paged')

    def prefill(self, params, cache, tokens, length, slot):
        self.cache_spec()

    def step(self, params, cache, tokens, positions):
        self.cache_spec()

    def paged_verify(self, params, pool, tokens, positions, tables,
                     ad=None):
        raise FamilyUnsupported(
            self.family, 'paged_verify (speculative decoding): the '
            'absorbed attention takes one query row a slot, and nothing '
            'drafts for it')

    def lora_targets(self):
        raise FamilyUnsupported(
            self.family, 'lora_targets (low-rank adapters): no adapter '
            'layout is defined for the low-rank projections or for '
            'stacked expert weights')

    # -- block math ----------------------------------------------------------

    def _rms(self, x, g):
        import jax
        import jax.numpy as jnp
        x = x.astype('float32')
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) \
            * g.astype('float32')

    def _mm(self, spec, a, b):
        return blocks.mm(spec, a, b, self.dtype)

    def _rope(self, x, positions):
        """Rotate the last axis of x (T, ..., rope) by YaRN's angles at
        ``positions`` (T,): pairs are (j, j + rope / 2)."""
        import jax.numpy as jnp
        ang = positions.astype('float32')[:, None] \
            * jnp.asarray(self._inv_freq)[None]
        ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
        cos = jnp.cos(ang) * self._rope_scale
        sin = jnp.sin(ang) * self._rope_scale
        x = x.astype('float32')
        a, b = x[..., :self.rope // 2], x[..., self.rope // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def _coefficients(self, p, j, xs):
        """A sublayer's read, write and mixing coefficients from the
        streams ``xs`` (n arrays (T, C)): Hpre (T, n), Hpost (T, n),
        Hres (T, n, n) doubly stochastic, all float32. The norm is over
        all n C values of a token; stream i meets rows i C .. (i + 1) C
        of ``phi``."""
        import jax
        import jax.numpy as jnp
        n, c, t = self.n, self.hidden, xs[0].shape[0]
        g = p('hc%d_g' % j).astype('float32').reshape(n, c)
        phi = p('hc%d_phi' % j).astype('float32').reshape(n, c, -1)
        inv = jax.lax.rsqrt(
            sum(jnp.sum(jnp.square(x), axis=-1, keepdims=True) for x in xs)
            / (n * c) + self.eps)
        z = sum(jnp.einsum('tc,co->to', x * inv * g[i], phi[i],
                           precision=jax.lax.Precision.HIGHEST)
                for i, x in enumerate(xs))
        a, b = p('hc%d_a' % j), p('hc%d_b' % j)
        pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
        m = jnp.exp(jnp.clip(a[2] * z[:, 2 * n:] + b[2 * n:],
                             self.hc_lo, self.hc_hi)).reshape(t, n, n)
        for _ in range(self.hc_iters):
            m = m / (jnp.sum(m, axis=1, keepdims=True) + self.hc_eps)
            m = m / (jnp.sum(m, axis=2, keepdims=True) + self.hc_eps)
        return pre, post, m

    def _read(self, p, j, xs):
        """A block of tokens' coefficients and normed read stream:
        (Hpost (T, n), Hres (T, n, n), h (T, C))."""
        pre, post, res = self._coefficients(p, j, xs)
        read = sum(pre[:, i, None] * x for i, x in enumerate(xs))
        return post, res, self._rms(read, p('ln%d_g' % j))

    def _write(self, xs, post, res, out):
        """``Hres X + outer(Hpost, out)`` for a block of tokens."""
        return tuple(
            sum(res[:, i, k, None] * x for k, x in enumerate(xs))
            + post[:, i, None] * out for i in range(self.n))

    def _sublayer(self, p, j, xs, fn):
        """``X' = Hres X + outer(Hpost, F(RMSNorm_in(Hpre X)))`` over the
        streams ``xs``, n arrays (T, C) (one array a stream: a (T, n, C)
        array has its n = 4 in a tile's eight sublanes, and XLA keeps
        copies of it in two layouts); ``fn`` takes the normed read
        stream (T, C) and returns (its output (T, C), whatever else it
        has to hand on). A long sequence is read and written
        ``_STREAM_ROWS`` tokens at a time, the streams updated in place:
        what is computed from all n C values of a token is then a
        block's, not the sequence's."""
        import jax
        from jax import lax
        t, rows = xs[0].shape[0], _STREAM_ROWS
        blocked = t > rows and t % rows == 0
        with jax.named_scope('hyper_connection'):
            if blocked:
                post, res, h = (
                    v.reshape((t,) + v.shape[2:]) for v in lax.map(
                        lambda xb: self._read(p, j, xb),
                        tuple(x.reshape(t // rows, rows, -1) for x in xs)))
            else:
                post, res, h = self._read(p, j, xs)
        out, rest = fn(h)
        with jax.named_scope('hyper_connection'):
            if not blocked:
                return self._write(xs, post, res, out), rest

            def one_block(i, xs):
                at = i * rows
                cut = lambda v: lax.dynamic_slice_in_dim(  # noqa: E731
                    v, at, rows, 0)
                new = self._write(tuple(cut(x) for x in xs), cut(post),
                                  cut(res), cut(out))
                return tuple(lax.dynamic_update_slice_in_dim(x, v, at, 0)
                             for x, v in zip(xs, new))

            return lax.fori_loop(0, t // rows, one_block, xs), rest

    def _queries(self, p, h, positions):
        """q_nope (T, heads, nope) and the roped q_pe (T, heads, rope),
        float32."""
        t = h.shape[0]
        cq = self._rms(self._mm('th,hr->tr', h, p('q_a')), p('q_a_g'))
        q = self._mm('tr,ro->to', cq, p('q_b')).reshape(
            t, self.heads, self.nope + self.rope)
        return q[..., :self.nope], self._rope(q[..., self.nope:], positions)

    def _cache_rows(self, p, h, positions):
        """What a token leaves in the cache: [RMSNorm(c) | rope(k_pe) |
        zeros] (T, row_width) in ``dtype``."""
        import jax.numpy as jnp
        kv = self._mm('th,hr->tr', h, p('kv_a'))
        c = self._rms(kv[:, :self.kv_rank], p('kv_a_g'))
        k_pe = self._rope(kv[:, self.kv_rank:], positions)
        pad = self.row_width - self.kv_rank - self.rope
        return jnp.concatenate(
            [c, k_pe, jnp.zeros((h.shape[0], pad), 'float32')],
            axis=-1).astype(self.dtype)

    def _kv_b(self, p):
        """``Wkvb`` by head: the key half (kv_rank, heads, nope) and the
        value half (kv_rank, heads, v)."""
        w = p('kv_b').reshape(self.kv_rank, self.heads,
                              self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def _attention_sequence(self, p, h, positions):
        """The prefill's attention over one whole sequence h (S, C):
        latent rows up-projected to heads, causal, a block of queries at
        a time. Queries, keys and values are projected ``_STREAM_ROWS``
        rows at a time and kept in ``dtype``. Returns (out (S, C), the
        cache rows (S, row_width))."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        s, rows_at_once = h.shape[0], _STREAM_ROWS
        wk, wv = self._kv_b(p)

        def project(args):
            hb, at = args
            q_nope, q_pe = self._queries(p, hb, at)
            rows = self._cache_rows(p, hb, at)
            c = rows[:, :self.kv_rank]
            k_pe = rows[:, self.kv_rank:self.kv_rank + self.rope]
            k = jnp.concatenate(
                [self._mm('sc,chd->shd', c, wk).astype(self.dtype),
                 jnp.broadcast_to(k_pe[:, None],
                                  (hb.shape[0], self.heads, self.rope))], -1)
            q = jnp.concatenate(
                [(q_nope * self.score_scale).astype(self.dtype),
                 (q_pe * self.score_scale).astype(self.dtype)], -1)
            return q, k, self._mm('sc,chd->shd', c, wv).astype(self.dtype), \
                rows

        with jax.named_scope('attn'):
            if s > rows_at_once and s % rows_at_once == 0:
                q, k, v, rows = (
                    a.reshape((s,) + a.shape[2:]) for a in lax.map(
                        project, (h.reshape(-1, rows_at_once, h.shape[1]),
                                  positions.reshape(-1, rows_at_once))))
            else:
                q, k, v, rows = project((h, positions))
            ctx = blocks.attend_blocks(q[:, :, None], k, v,
                                       self.prefill_block, None, self.dtype)
            return self._mm('to,oh->th', ctx, p('o_w')), rows

    def _latent_values(self, rows):
        """The values of gathered cache rows: their latent columns."""
        return rows[..., :self.kv_rank]

    def _attention_step(self, p, h, pool, key, positions, tables):
        """The step's attention: append this token's latent row
        (``pool`` is updated in place, a dict) and attend the latent
        rows each slot's position has seen, ``Wkvb`` absorbed into the
        query and the output."""
        import jax
        import jax.numpy as jnp
        slots = h.shape[0]
        ps = pool[key].shape[1]
        at = jnp.take_along_axis(tables, (positions // ps)[:, None],
                                 axis=1)[:, 0]
        with jax.named_scope('mla_absorb'):
            q_nope, q_pe = self._queries(p, h, positions)
            pool[key] = scatter_rows(
                pool[key], self._cache_rows(p, h, positions), at,
                positions % ps)
            wk, wv = self._kv_b(p)
            pad = self.row_width - self.kv_rank - self.rope
            q = jnp.concatenate(
                [self._mm('thd,chd->thc', q_nope, wk), q_pe,
                 jnp.zeros((slots, self.heads, pad), 'float32')], -1) \
                * self.score_scale
        with jax.named_scope('mla_walk'):
            if walks_pages(pool[key].shape, pool[key].dtype):
                from ...ops.pallas import flash_paged_decode_attention
                # q carries the score scale already
                ctx = flash_paged_decode_attention(
                    q.reshape(slots, -1), pool[key], None, tables,
                    positions, heads=self.heads, scale=1.0,
                    value_cols=self.kv_rank
                ).reshape(slots, self.heads, self.kv_rank)
            else:
                rows = gather_pages(pool[key], tables)
                seen = jnp.arange(rows.shape[1])[None] <= positions[:, None]
                scores = self._mm('thw,tlw->thl', q, rows) \
                    + jnp.where(seen, 0.0, -1e9)[:, None]
                ctx = self._mm('thl,tlc->thc', blocks.softmax(scores),
                               self._latent_values(rows))
        with jax.named_scope('mla_absorb'):
            out = self._mm('thc,chd->thd', ctx, wv)
            return self._mm('to,oh->th', out.reshape(slots, -1), p('o_w'))

    def _route(self, p, h):
        """Sigmoid scores over all experts (float32, highest
        precision), the ``top_k`` largest of score + bias, and the
        selected scores normalised over all of them and scaled:
        (weights (T, K) float32, expert ids (T, K))."""
        import jax
        import jax.numpy as jnp
        scores = jax.nn.sigmoid(jnp.einsum(
            'th,he->te', h.astype('float32'),
            p('router_w').astype('float32'),
            precision=jax.lax.Precision.HIGHEST))
        _, top_i = jax.lax.top_k(scores + p('router_b'), self.top_k)
        top_s = jnp.take_along_axis(scores, top_i, axis=1)
        return self.routed_scale * top_s \
            / jnp.sum(top_s, axis=-1, keepdims=True), top_i

    def _dense_mlp(self, p, h):
        import jax
        import jax.numpy as jnp
        from jax import lax

        def ffn(rows):
            return blocks.gated_ffn('th,hf->tf', 'tf,fh->th', rows,
                                    p('d1'), p('d3'), p('d2'), self.dtype)

        with jax.named_scope('mlp'):
            t = h.shape[0]
            if t <= _MLP_ROWS or t % _MLP_ROWS:
                return ffn(h)
            return lax.map(ffn, h.reshape(-1, _MLP_ROWS, h.shape[1])
                           ).reshape(t, -1)

    def _mlp(self, p, dense, layer, rows):
        """The second sublayer's F: a dense MLP, or routed + shared
        experts. ``layer`` is the step's or the prefill's expert layer
        (``HeldExperts.dense`` / ``grouped``) and ``rows`` what it takes
        after the gates: the live slots, or the prompt's length. Returns
        a function of the normed read stream giving (y, counts)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        if dense:
            return lambda h: (self._dense_mlp(p, h),
                              jnp.zeros((1,), 'int32'))

        def experts(h, rows):
            with jax.named_scope('router'):
                w, top_i = self._route(p, h)
            out, counts = layer(h, w, top_i, rows, p('w1'), p('w3'),
                                p('w2'))
            with jax.named_scope('shared'):
                shared = blocks.gated_ffn(
                    'th,hf->tf', 'tf,fh->th', h, p('s1'), p('s3'),
                    p('s2'), self.dtype)
            return out + shared, counts

        def moe(h):
            t, r = h.shape[0], _MOE_ROWS
            with jax.named_scope('moe'):
                if t <= r or t % r:
                    return experts(h, rows)
                # a long prefill, _MOE_ROWS tokens at a time: the sort
                # and the rows gathered for the held experts are a
                # block's; ``rows`` is the prompt's length
                real = jnp.clip(rows - jnp.arange(t // r) * r, 0, r)
                out, counts = lax.map(
                    lambda a: experts(*a),
                    (h.reshape(t // r, r, h.shape[1]), real))
                return out.reshape(t, -1), jnp.sum(counts, axis=0)
        return moe

    def _embed(self, params, tokens):
        """tokens (T,) -> the n streams, each (T, C) float32."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope('embed'):
            e = jnp.take(params['embed'], tokens, axis=0).astype('float32')
            return (e,) * self.n

    def _head(self, params, summed, g='lnf_g'):
        import jax
        with jax.named_scope('lm_head'):
            return self._mm('...h,vh->...v',
                            self._rms(summed, params[g]), params['head'])

    def _layer_sequence(self, p, x, dense, length, positions):
        """One layer over one whole sequence's streams x (n arrays (S,
        C)): (x', the cache rows it leaves (S, row_width))."""
        x, rows = self._sublayer(
            p, 1, x, lambda h: self._attention_sequence(p, h, positions))
        x, _counts = self._sublayer(
            p, 2, x, self._mlp(p, dense, self._experts.grouped, length))
        return x, rows

    def _sequence_pass(self, params, tokens, length):
        """One whole sequence, tokens (S,): the summed streams before
        the final norm (S, C) and each layer's cache rows. Rows at or
        past ``length`` are padding: causal attention keeps them from
        every real row and the router sends them nowhere. The prefill
        AND the uncached reference pass."""
        import jax
        import jax.numpy as jnp
        positions = jnp.arange(tokens.shape[0])
        x = self._embed(params, tokens)
        left = []
        for i in range(self.layers):
            p = lambda name: params['l%d_%s' % (i, name)]  # noqa: E731
            with jax.named_scope('layer%d' % i):
                x, rows = self._layer_sequence(
                    p, x, i < self.dense_layers, length, positions)
                left.append(rows)
        return sum(x), left

    def full_forward(self, params, tokens):
        """tokens (B, T) -> logits (B, T, V), no cache."""
        import jax.numpy as jnp
        t = tokens.shape[1]
        return jnp.stack([
            self._head(params, self._sequence_pass(params, row, t)[0])
            for row in tokens])

    def mtp_logits(self, params, tokens):
        """The next-token module over one sequence tokens (T,): row t
        (of T - 1) holds the logits of token t + 2, from the main
        model's summed streams at t and the embedding of token t + 1."""
        import jax
        import jax.numpy as jnp
        if not self.mtp:
            raise ValueError('the configuration has no next-token module '
                             '(mtp)')
        t = tokens.shape[0]
        summed, _ = self._sequence_pass(params, tokens, t)
        p = lambda name: params['mtp_' + name]             # noqa: E731
        with jax.named_scope('mtp'):
            nxt = jnp.take(params['embed'], tokens[1:],
                           axis=0).astype('float32')
            h = self._mm('tk,kh->th', jnp.concatenate(
                [self._rms(summed[:-1], p('hnorm_g')),
                 self._rms(nxt, p('enorm_g'))], -1), p('proj'))
            x, _rows = self._layer_sequence(p, (h,) * self.n, False, t - 1,
                                            jnp.arange(t - 1))
            return self._head(params, sum(x), 'mtp_lnf_g')

    # -- paged cache paths ---------------------------------------------------

    def paged_spec(self, page_size):
        """One paged entry a layer, ``l<i>_c``: the latent rows, one
        (pages, page_size, row_width) pool (paged.PagedCacheSpec)."""
        return PagedCacheSpec(
            {'l%d_c' % i: ((self.row_width,), self.dtype)
             for i in range(self.layers)},
            page_size, self.max_len, entries_per_layer=1)

    def paged_prefill(self, params, pool, tokens, length, page_ids,
                      ad=None):
        """Prefill through the page table: tokens (1, S); every layer's
        latent rows land in ``page_ids``. Returns (pool', logits (V,)
        at position ``length - 1``)."""
        import jax.numpy as jnp
        from jax import lax
        del ad
        s = tokens.shape[1]
        summed, left = self._sequence_pass(params, tokens[0], length)
        pool = dict(pool)
        for i, rows in enumerate(left):
            key = 'l%d_c' % i
            pad = page_ids.shape[0] * pool[key].shape[1] - s
            pool[key] = scatter_pages(
                pool[key], jnp.pad(rows, ((0, pad), (0, 0))), page_ids)
        last = lax.dynamic_slice_in_dim(summed, length - 1, 1, 0)[0]
        return pool, self._head(params, last)

    def paged_step(self, params, pool, tokens, positions, tables,
                   ad=None):
        """One decode step: tokens / positions (slots,); every layer
        appends its latent row and attends through ``tables`` (slots,
        max_pages). A slot is live iff its position is above 0. Returns
        (pool', logits (slots, V), counts (3,) int32 in
        ``step_stats``' order: live slots x top_k x expert layers, the
        assignments among them that landed on a held expert, and the
        largest count one held expert of one layer saw)."""
        import jax
        import jax.numpy as jnp
        del ad
        live = positions > 0
        x = self._embed(params, tokens)
        pool = dict(pool)
        here = jnp.zeros((), 'int32')
        load = jnp.zeros((), 'int32')
        for i in range(self.layers):
            p = lambda name: params['l%d_%s' % (i, name)]  # noqa: E731
            with jax.named_scope('layer%d' % i):
                x, _ = self._sublayer(
                    p, 1, x, lambda h: (self._attention_step(
                        p, h, pool, 'l%d_c' % i, positions, tables), None))
                x, counts = self._sublayer(
                    p, 2, x, self._mlp(p, i < self.dense_layers,
                                       self._experts.dense, live))
                here = here + jnp.sum(counts)
                load = jnp.maximum(load, jnp.max(counts))
        routed = jnp.sum(live).astype('int32') \
            * (self.top_k * (self.layers - self.dense_layers))
        return pool, self._head(params, sum(x)), \
            jnp.stack([routed, here, load])

    # -- construction --------------------------------------------------------

    def _layer_shapes(self, prefix, dense):
        c, n, h = self.hidden, self.n, self.heads
        shapes = {
            'ln1_g': (c,), 'ln2_g': (c,),
            'q_a': (c, self.q_rank), 'q_a_g': (self.q_rank,),
            'q_b': (self.q_rank, h * (self.nope + self.rope)),
            'kv_a': (c, self.kv_rank + self.rope),
            'kv_a_g': (self.kv_rank,),
            'kv_b': (self.kv_rank, h * (self.nope + self.v_dim)),
            'o_w': (h * self.v_dim, c)}
        for j in (1, 2):
            shapes.update({
                'hc%d_g' % j: (n * c,),
                'hc%d_phi' % j: (n * c, 2 * n + n * n),
                'hc%d_a' % j: (3,), 'hc%d_b' % j: (2 * n + n * n,)})
        if dense:
            d = self.dense_hidden
            shapes.update({'d1': (c, d), 'd3': (c, d), 'd2': (d, c)})
        else:
            f, fs, eh = self.expert_hidden, self.shared_hidden, \
                len(self.held)
            shapes.update({
                'router_w': (c, self.experts), 'router_b': (self.experts,),
                'w1': (eh, c, f), 'w3': (eh, c, f), 'w2': (eh, f, c),
                's1': (c, fs), 's3': (c, fs), 's2': (fs, c)})
        return {prefix + k: v for k, v in shapes.items()}

    def param_shapes(self):
        c = self.hidden
        shapes = {'embed': (self.vocab, c), 'head': (self.vocab, c),
                  'lnf_g': (c,)}
        for i in range(self.layers):
            shapes.update(self._layer_shapes('l%d_' % i,
                                             i < self.dense_layers))
        if self.mtp:
            shapes.update({'mtp_hnorm_g': (c,), 'mtp_enorm_g': (c,),
                           'mtp_lnf_g': (c,), 'mtp_proj': (2 * c, c)})
            shapes.update(self._layer_shapes('mtp_', False))
        return shapes

    def init_params(self, seed=0):
        """Seeded leaves for tests (the benchmark makes its own): normal
        at 1/sqrt(fan-in), gains at 1; a hyper-connection's ``a`` at 1,
        ``b_pre`` and ``b_post`` 0 and ``b_res`` 4 on the diagonal with
        a little noise everywhere; a router bias that moves selections."""
        import jax.numpy as jnp
        rs = onp.random.RandomState(seed)
        n = self.n
        out = {}
        for name, shape in self.param_shapes().items():
            leaf = re.sub(r'^(l\d+|mtp)_', '', name)
            if leaf.endswith('_g'):
                out[name] = jnp.ones(shape, self.dtype)
            elif re.fullmatch(r'hc\d_a', leaf):
                out[name] = jnp.ones(shape, 'float32')
            elif re.fullmatch(r'hc\d_b', leaf):
                b = 0.1 * rs.randn(*shape)
                b[2 * n:] += 4.0 * onp.eye(n).reshape(-1)
                out[name] = jnp.asarray(b, 'float32')
            elif leaf == 'router_b':
                out[name] = jnp.asarray(0.05 * rs.randn(*shape), 'float32')
            elif leaf == 'embed':
                out[name] = jnp.asarray(rs.randn(*shape), self.dtype)
            elif leaf == 'head':
                out[name] = jnp.asarray(
                    rs.randn(*shape) / onp.sqrt(shape[-1]), self.dtype)
            else:
                out[name] = jnp.asarray(
                    rs.randn(*shape) / onp.sqrt(shape[-2]), self.dtype)
        return out


_FAMILIES[Xing4LM.family] = Xing4LM


def init_xing4_lm(seed=0, **config):
    """Deterministic small model of the family: (model, params)."""
    small = dict(vocab=96, max_len=96, hidden=48, layers=3, dense_layers=1,
                 eps=1e-6, heads=4, q_rank=24, kv_rank=32, nope_dim=16,
                 rope_dim=8, v_dim=16, dense_hidden=96, experts=8,
                 held_experts=list(range(8)), top_k=2, expert_hidden=32,
                 shared_hidden=32, routed_scale=2.0, hc_mult=4,
                 hc_iters=20, hc_eps=1e-6, hc_clamp=(-30.0, 30.0),
                 rope_theta=10000.0,
                 yarn=dict(factor=4.0, original_max=32, beta_fast=32.0,
                           beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
                 dtype='float32')
    small.update(config)
    model = Xing4LM(small)
    return model, model.init_params(seed)
