"""Decode model families: one math path for prefill, step, and the
uncached reference forward.

The engine's correctness contract is *bit-identity*: N tokens decoded
through the cache must equal the N tokens you would get by re-running
the whole-sequence forward after every token and slicing its last
position. That only holds when prefill, decode step, and the
reference forward share one set of primitive contractions — so each
family implements all three from the same cell/attention code:

  * :class:`RNNLM`         — Embedding -> fused multi-layer
    LSTM/GRU/RNN (the exact ``ops/nn.py`` cell math the training path
    scans with) -> Dense head. The recurrent state IS the cache:
    per-slot ``(layers, hidden)`` carried tensors, O(1) per token by
    construction. Built from trained gluon blocks via
    :func:`from_gluon_rnn_lm` (``gluon/rnn/rnn_layer.py`` layers).
  * :class:`TransformerLM` — causal decoder with
    ``gluon/model_zoo/bert.py``-style blocks (fused QKV, post-norm
    residual cells, gelu FFN, tied embedding head) and a preallocated
    per-slot KV cache ``(max_len, units)`` per layer appended via
    ``lax.dynamic_update_slice`` (cache.write_position).

Why padded prefill stays bit-exact: bucket padding adds key rows whose
attention weights underflow to exact 0.0 (additive -1e9 mask) and
whose RNN state updates are frozen by a ``t < length`` select, so
every real position's reduction tree combines the same values plus
exact zeros — adding 0.0 is bitwise-identity for finite floats, the
same argument bucket.py makes for batch padding.
"""
from __future__ import annotations

import numpy as onp

from .cache import CacheSpec, write_position, write_slot
from .paged import (PagedCacheSpec, gather_pages, scatter_rows,
                    walks_pages, write_prefill_pages)

__all__ = ['DecodeModel', 'RNNLM', 'TransformerLM', 'from_gluon_rnn_lm',
           'model_from_config', 'init_rnn_lm', 'init_transformer_lm',
           'FamilyUnsupported']


class FamilyUnsupported(NotImplementedError):
    """A decode family was asked for a path it does not implement
    (docs/SERVING.md lists them per family): names the family and the
    path, and is raised where the path is asked for, at freeze time
    wherever that is possible."""

    def __init__(self, family, what):
        super().__init__('decode family %r does not implement %s'
                         % (family, what))
        self.family, self.what = family, what


def _as_numpy(arr):
    if hasattr(arr, 'asnumpy'):
        return arr.asnumpy()
    return onp.asarray(arr)


def _flash_on():
    """Flash-attention gate (MXNET_TPU_PALLAS=attention): snapshot-
    first via ops.traceknobs — DecodeProgram installs the build-time
    snapshot over its traces and keys the compiled programs on it, so
    a knob flip re-jits instead of latching (docs/PERFORMANCE.md)."""
    from ...ops.pallas import enabled
    return enabled('attention')


class DecodeModel:
    """Interface one decode family implements (pure functions over a
    ``{name: array}`` params dict; no state on the model object):

      * ``cache_spec()``                         -> :class:`CacheSpec`
      * ``prefill(params, cache, tokens, length, slot)``
          tokens (1, S) int32, length/slot traced scalars
          -> (cache', logits (V,)) at position length-1
      * ``step(params, cache, tokens, positions)``
          tokens/positions (slots,) int32
          -> (cache', logits (slots, V))
      * ``full_forward(params, tokens)``
          tokens (B, T) int32 -> logits (B, T, V) — the uncached
          reference the bit-identity tests slice
    """

    family = None
    # paged KV caches need a position-addressed history (rewriting a
    # rejected position must be free); an RNN's carried state is O(1)
    # per slot already — there is no memory wall to page
    supports_paging = False

    def __init__(self, config):
        self.config = dict(config)
        self.vocab = int(config['vocab'])
        self.max_len = int(config['max_len'])

    def cache_spec(self):
        raise NotImplementedError

    def prefill(self, params, cache, tokens, length, slot):
        raise NotImplementedError

    def step(self, params, cache, tokens, positions):
        raise NotImplementedError

    def full_forward(self, params, tokens):
        raise NotImplementedError

    def init_params(self, seed=0):
        raise NotImplementedError

    def __repr__(self):
        return '%s(%r)' % (type(self).__name__, self.config)


# ---------------------------------------------------------------------------
# RNN language model (state cache; O(1) per token by construction)
# ---------------------------------------------------------------------------

class RNNLM(DecodeModel):
    """Embedding -> multi-layer {lstm,gru,rnn_relu,rnn_tanh} -> Dense.

    config: vocab, embed, hidden, layers, mode, max_len.
    params: embed_weight (V, E), rnn_params (flat cuDNN layout — the
    same vector gluon ``_RNNLayer._flat_params`` feeds the fused RNN
    op), out_weight (V, H), out_bias (V,).
    """

    family = 'rnn_lm'

    def __init__(self, config):
        super().__init__(config)
        self.mode = str(config['mode'])
        if self.mode not in ('lstm', 'gru', 'rnn_relu', 'rnn_tanh'):
            raise ValueError('unsupported RNN mode %r' % self.mode)
        self.embed = int(config['embed'])
        self.hidden = int(config['hidden'])
        self.layers = int(config['layers'])

    # state carried per slot: (layers, hidden) per state tensor
    def cache_spec(self):
        entries = {'h': ((self.layers, self.hidden), 'float32')}
        if self.mode == 'lstm':
            entries['c'] = ((self.layers, self.hidden), 'float32')
        return CacheSpec(entries)

    def _unpacked(self, params):
        from ...ops.nn import _rnn_unpack_params
        Ws, Bs = _rnn_unpack_params(
            params['rnn_params'], self.mode, self.layers, self.embed,
            self.hidden, bidirectional=False)
        return Ws, Bs

    def _scan_layers(self, params, x, h0, c0, length=None):
        """Shared sequence pass: x (T, B, E) -> (ys (T, B, H), hT, cT).

        ``length`` (scalar) freezes state updates at t >= length — the
        padded-prefill mask; None runs every step (reference path).
        h0/c0: (layers, B, H).
        """
        import jax
        import jax.numpy as jnp
        from ...ops.nn import _cell_step
        Ws, Bs = self._unpacked(params)
        T = x.shape[0]
        steps = jnp.arange(T)
        hs, cs = [], []
        for layer in range(self.layers):
            (w_i2h, w_h2h) = Ws[layer][0]
            (b_i2h, b_h2h) = Bs[layer][0]
            # input projection for the whole sequence as one matmul
            # (the fused-RNN idiom; per-row dots match the step path)
            xw = jnp.einsum('tbi,gi->tbg', x, w_i2h) + b_i2h

            def cell(carry, scan_in, w_h2h=w_h2h, b_h2h=b_h2h):
                xw_t, t = scan_in
                new, y = _cell_step(self.mode, carry, xw_t, w_h2h,
                                    b_h2h)
                if length is not None:
                    keep = t < length
                    new = tuple(jnp.where(keep, n, o)
                                for n, o in zip(new, carry))
                    y = jnp.where(keep, y, jnp.zeros_like(y))
                return new, y

            carry = (h0[layer], c0[layer]) if self.mode == 'lstm' \
                else (h0[layer],)
            carry, ys = jax.lax.scan(cell, carry, (xw, steps))
            hs.append(carry[0])
            if self.mode == 'lstm':
                cs.append(carry[1])
            x = ys
        hT = jnp.stack(hs, axis=0)
        cT = jnp.stack(cs, axis=0) if cs else None
        return x, hT, cT

    def _head(self, params, h):
        import jax.numpy as jnp
        return jnp.einsum('...h,vh->...v', h, params['out_weight']) \
            + params['out_bias']

    def prefill(self, params, cache, tokens, length, slot):
        import jax.numpy as jnp
        S = tokens.shape[1]
        x = jnp.take(params['embed_weight'], tokens[0], axis=0)  # (S, E)
        x = x[:, None, :]                                # (T, B=1, E)
        zeros = jnp.zeros((self.layers, 1, self.hidden), 'float32')
        ys, hT, cT = self._scan_layers(params, x, zeros, zeros,
                                       length=length)
        # state after `length` real steps == state the step path will
        # carry forward; land it in the slot
        cache = dict(cache)
        cache['h'] = write_slot(cache['h'], hT[:, 0], slot)
        if cT is not None:
            cache['c'] = write_slot(cache['c'], cT[:, 0], slot)
        # logits at the last real position = head(h of the top layer)
        # — the frozen scan's final top-layer h IS h_{length-1}
        return cache, self._head(params, hT[-1, 0])

    def step(self, params, cache, tokens, positions):
        import jax.numpy as jnp
        from ...ops.nn import _cell_step
        del positions                       # state cache is positionless
        Ws, Bs = self._unpacked(params)
        x = jnp.take(params['embed_weight'], tokens, axis=0)  # (S, E)
        h = cache['h']                      # (slots, layers, H)
        c = cache.get('c')
        new_h, new_c = [], []
        for layer in range(self.layers):
            (w_i2h, w_h2h) = Ws[layer][0]
            (b_i2h, b_h2h) = Bs[layer][0]
            xw = jnp.einsum('bi,gi->bg', x, w_i2h) + b_i2h
            carry = (h[:, layer], c[:, layer]) if self.mode == 'lstm' \
                else (h[:, layer],)
            carry, y = _cell_step(self.mode, carry, xw, w_h2h, b_h2h)
            new_h.append(carry[0])
            if self.mode == 'lstm':
                new_c.append(carry[1])
            x = y
        cache = dict(cache)
        cache['h'] = jnp.stack(new_h, axis=1)       # (slots, layers, H)
        if new_c:
            cache['c'] = jnp.stack(new_c, axis=1)
        return cache, self._head(params, x)

    def full_forward(self, params, tokens):
        import jax.numpy as jnp
        B = tokens.shape[0]
        x = jnp.take(params['embed_weight'], tokens, axis=0)  # (B,T,E)
        x = jnp.transpose(x, (1, 0, 2))                       # (T,B,E)
        zeros = jnp.zeros((self.layers, B, self.hidden), 'float32')
        ys, _, _ = self._scan_layers(params, x, zeros, zeros)
        return jnp.transpose(self._head(params, ys), (1, 0, 2))

    def init_params(self, seed=0):
        from ...ops.nn import rnn_param_size
        rs = onp.random.RandomState(seed)
        n = rnn_param_size(self.mode, self.layers, self.embed,
                           self.hidden, False)
        return {
            'embed_weight': rs.randn(self.vocab, self.embed)
            .astype('float32') * 0.1,
            'rnn_params': rs.randn(n).astype('float32') * 0.1,
            'out_weight': rs.randn(self.vocab, self.hidden)
            .astype('float32') * 0.1,
            'out_bias': onp.zeros(self.vocab, 'float32'),
        }


# ---------------------------------------------------------------------------
# Causal transformer language model (per-layer KV cache)
# ---------------------------------------------------------------------------

class TransformerLM(DecodeModel):
    """Causal decoder over bert.py-style blocks with a preallocated
    KV cache.

    config: vocab, units, hidden, layers, heads, max_len, eps.
    params: embed (V, U), pos (max_len, U), out_bias (V,) (head tied
    to ``embed`` like the BERT MLM decoder), and per layer ``l{i}_``:
    qkv_w (3U, U), qkv_b, out_w (U, U), out_b, ln1_g/ln1_b,
    ffn1_w (H, U), ffn1_b, ffn2_w (U, H), ffn2_b, ln2_g/ln2_b.
    """

    family = 'transformer_lm'

    def __init__(self, config):
        config = dict(config)
        config.setdefault('eps', 1e-12)
        super().__init__(config)
        self.units = int(config['units'])
        self.hidden = int(config['hidden'])
        self.layers = int(config['layers'])
        self.heads = int(config['heads'])
        self.eps = float(config['eps'])
        if self.units % self.heads:
            raise ValueError('units %d not divisible by heads %d'
                             % (self.units, self.heads))

    def cache_spec(self):
        return CacheSpec({
            'l%d_%s' % (i, kv): ((self.max_len, self.units), 'float32')
            for i in range(self.layers) for kv in ('k', 'v')})

    # -- low-rank adapters (serving/adapters/, docs/SERVING.md
    # "Multi-adapter serving & sampling") ----------------------------------

    def lora_targets(self):
        """The projections an adapter may delta, with their
        (out, in) dims — the shapes ``serving.adapters`` sizes its
        A/B pool entries to. Per-layer names follow the params dict
        (``l{i}_qkv`` etc.)."""
        U, H = self.units, self.hidden
        return {'qkv': (3 * U, U), 'ffn1': (H, U), 'ffn2': (U, H)}

    @staticmethod
    def _lora_delta(x, a, b):
        """Low-rank delta ``(x @ A^T) @ B^T`` — scale is folded into B
        at pool-load time. ``a``/``b`` 2-D is ONE shared adapter
        (prefill: a (r, in), b (out, r)); 3-D is the per-slot gathered
        stack (a (s, r, in), b (s, out, r)) applied to x (s, ..., in).
        The pool's reserved zero entry makes the base path exact: the
        delta is 0.0 everywhere and additive 0.0 changes no argmax."""
        import jax.numpy as jnp
        if a.ndim == 2:
            h = jnp.einsum('...i,ri->...r', x, a)
            return jnp.einsum('...r,or->...o', h, b)
        h = jnp.einsum('s...i,sri->s...r', x, a)
        return jnp.einsum('s...r,sor->s...o', h, b)

    def _adapted(self, x, w, b, ad, key):
        """Dense projection plus the (optional) gathered adapter
        delta. ``ad`` maps ``l{i}_{target}`` -> (A, B) arrays already
        selected for this call's slots; None is the no-adapter fast
        path (the traced graph is unchanged — not merely zero)."""
        y = self._dense(x, w, b)
        if ad is not None and key in ad:
            la, lb = ad[key]
            y = y + self._lora_delta(x, la, lb)
        return y

    # -- shared block math --------------------------------------------------

    def _ln(self, x, g, b):
        import jax.numpy as jnp
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + self.eps) * g + b

    def _dense(self, x, w, b):
        import jax.numpy as jnp
        return jnp.einsum('...i,oi->...o', x, w) + b

    def _heads_split(self, x):
        # (..., S, U) -> (..., S, H, D)
        return x.reshape(x.shape[:-1] + (self.heads,
                                         self.units // self.heads))

    # Every operation of a compiled decode program carries the scope it
    # was traced under in its ``op_name`` (metadata only: the compiled
    # code is the same): ``embed``, ``layer<i>/attn``,
    # ``layer<i>/kv_gather`` (paged.gather_pages), ``layer<i>/ffn``,
    # ``lm_head``, and ``sampling`` (sampling.sample_tokens).

    def _embed(self, params, tokens, positions):
        import jax
        import jax.numpy as jnp
        with jax.named_scope('embed'):
            return jnp.take(params['embed'], tokens, axis=0) \
                + jnp.take(params['pos'], positions, axis=0)

    def _ffn_block(self, params, i, x, ad=None):
        import jax
        p = lambda n: params['l%d_%s' % (i, n)]           # noqa: E731
        with jax.named_scope('ffn'):
            h = jax.nn.gelu(self._adapted(x, p('ffn1_w'), p('ffn1_b'),
                                          ad, 'l%d_ffn1' % i),
                            approximate=False)
            return self._ln(
                x + self._adapted(h, p('ffn2_w'), p('ffn2_b'),
                                  ad, 'l%d_ffn2' % i),
                p('ln2_g'), p('ln2_b'))

    def _head(self, params, h):
        import jax
        import jax.numpy as jnp
        with jax.named_scope('lm_head'):
            return jnp.einsum('...u,vu->...v', h, params['embed']) \
                + params['out_bias']

    def _attend_view(self, q, keys, values, bias):
        """C query rows a slot over its own (slots, L, units) keys and
        values (the slot cache's rows, or the pages a table gathered):
        q (slots, C, units) already scaled, bias (slots, C, L) masking
        what lies beyond each query's position. Returns (slots, C,
        units).

        No head is split out of the view. A head's query is laid over
        all ``units`` columns, zero outside the head's own, so both
        contractions run over the rows as they lie in the pool, and
        each column of the context keeps its own head's sum. The zeros
        cost ``heads`` times the multiply-adds of a step that waits
        for memory; the split (slots, L, heads, D) cost a relayout of
        both views on a TPU, whose tiles want the minor dimension 128
        wide and get D: at GPT-1's size 28 ms of a 60 ms step once
        nothing else rewrote the view (PERF.md section 6, PR 31)."""
        import jax.numpy as jnp
        s, c, u = q.shape
        h = self.heads
        own = jnp.arange(u)[None, :] // (u // h) \
            == jnp.arange(h)[:, None]                     # (H, U)
        qx = jnp.where(own, q[:, :, None, :], 0.0).reshape(s, c * h, u)
        scores = jnp.einsum('smu,slu->sml', qx, keys).reshape(
            s, c, h, -1) + bias[:, :, None, :]
        att = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        cx = jnp.einsum('sml,slu->smu', att.reshape(s, c * h, -1),
                        values).reshape(s, c, h, u)
        return jnp.sum(jnp.where(own, cx, 0.0), axis=2)

    def _attend_rows(self, q, keys, values, bias):
        """One query row a slot: q (slots, units), bias (slots, 1, L)."""
        return self._attend_view(q[:, None], keys, values, bias)[:, 0]

    def _attn_out(self, params, i, x, ctx):
        """Output projection, residual and LayerNorm closing layer
        ``i``'s attention."""
        import jax
        p = lambda n: params['l%d_%s' % (i, n)]           # noqa: E731
        with jax.named_scope('attn'):
            return self._ln(
                x + self._dense(ctx, p('out_w'), p('out_b')),
                p('ln1_g'), p('ln1_b'))

    def _full_pass(self, params, tokens, length, ad=None):
        """Whole-sequence causal pass: tokens (B, S) -> (logits
        (B, S, V), per-layer k/v (B, S, U)). ``length`` masks padded
        keys (scalar or (B,)); the prefill AND reference path.
        ``ad`` — one shared adapter's (A, B) per target (prefill runs
        one sequence; its K/V land adapter-colored in the cache)."""
        import jax
        import jax.numpy as jnp
        B, S = tokens.shape
        positions = jnp.arange(S)
        x = self._embed(params, tokens, positions[None, :])
        ar = jnp.arange(S)
        # key j visible to query t iff j <= t (causal) and j < length
        mask = (ar[None, :] <= ar[:, None])[None] \
            & (ar[None, None, :] < jnp.reshape(
                jnp.asarray(length), (-1, 1, 1)))
        bias = jnp.where(mask, 0.0, -1e9)[:, None]     # (B, 1, S, S)
        scale = 1.0 / float(onp.sqrt(self.units // self.heads))
        flash = _flash_on()
        kvs = []
        for i in range(self.layers):
            p = lambda n: params['l%d_%s' % (i, n)]       # noqa: E731
            with jax.named_scope('layer%d' % i):
                with jax.named_scope('attn'):
                    qkv = self._adapted(x, p('qkv_w'), p('qkv_b'),
                                        ad, 'l%d_qkv' % i)
                    q, k, v = jnp.split(qkv, 3, axis=-1)
                    kvs.append((k, v))
                    if flash:
                        # blockwise online-softmax kernel over the
                        # padded prefix: masked keys carry exactly 0.0
                        # weight and the key axis walks the same fixed
                        # blocks the decode-step kernel walks, so the
                        # cached path combines the same reduction tree
                        # over the real keys (the bit-identity
                        # argument, module docstring)
                        from ...ops.pallas import flash_attention
                        ctx = flash_attention(
                            jnp.transpose(self._heads_split(q),
                                          (0, 2, 1, 3)),
                            jnp.transpose(self._heads_split(k),
                                          (0, 2, 1, 3)),
                            jnp.transpose(self._heads_split(v),
                                          (0, 2, 1, 3)),
                            lengths=length, causal=True, scale=scale)
                        ctx = jnp.transpose(ctx, (0, 2, 1, 3))
                    else:
                        qh = self._heads_split(q * scale)   # (B,S,H,D)
                        kh = self._heads_split(k)
                        vh = self._heads_split(v)
                        scores = jnp.einsum('bqhd,bkhd->bhqk',
                                            qh, kh) + bias
                        att = jnp.exp(scores - jnp.max(
                            scores, axis=-1, keepdims=True))
                        att = att / jnp.sum(att, axis=-1,
                                            keepdims=True)
                        ctx = jnp.einsum('bhqk,bkhd->bqhd', att, vh)
                    ctx = ctx.reshape(B, S, self.units)
                x = self._attn_out(params, i, x, ctx)
                x = self._ffn_block(params, i, x, ad)
        return self._head(params, x), kvs

    def prefill(self, params, cache, tokens, length, slot, ad=None):
        import jax.numpy as jnp
        S = tokens.shape[1]
        logits, kvs = self._full_pass(params, tokens, length, ad)
        cache = dict(cache)
        pad = self.max_len - S
        for i, (k, v) in enumerate(kvs):
            for name, arr in (('k', k), ('v', v)):
                # land the computed prefix; zero the tail so stale
                # values from the slot's previous occupant never sit
                # under a live sequence
                full = jnp.pad(arr[0], ((0, pad), (0, 0)))
                cache['l%d_%s' % (i, name)] = write_slot(
                    cache['l%d_%s' % (i, name)], full, slot)
        # logits at the last real position (length-1), one-hot dot so
        # the traced index stays inside the compiled program
        sel = (jnp.arange(S) == length - 1).astype(logits.dtype)
        return cache, jnp.einsum('s,sv->v', sel, logits[0])

    def step(self, params, cache, tokens, positions, ad=None):
        import jax
        import jax.numpy as jnp
        x = self._embed(params, tokens, positions)        # (S, U)
        ar = jnp.arange(self.max_len)
        # each slot attends its own history: j <= own position
        bias = jnp.where(ar[None, :] <= positions[:, None],
                         0.0, -1e9)[:, None, :]           # (S, 1, L)
        scale = 1.0 / float(onp.sqrt(self.units // self.heads))
        flash = _flash_on()
        cache = dict(cache)
        for i in range(self.layers):
            p = lambda n: params['l%d_%s' % (i, n)]       # noqa: E731
            with jax.named_scope('layer%d' % i):
                with jax.named_scope('attn'):
                    qkv = self._adapted(x, p('qkv_w'), p('qkv_b'),
                                        ad, 'l%d_qkv' % i)
                    q, k, v = jnp.split(qkv, 3, axis=-1)
                    ck = write_position(cache['l%d_k' % i], k,
                                        positions)
                    cv = write_position(cache['l%d_v' % i], v,
                                        positions)
                    cache['l%d_k' % i], cache['l%d_v' % i] = ck, cv
                    if flash:
                        # single-token kernel reading the slot cache
                        # in its native (slots, max_len, units) layout
                        # — no per-step head transpose of the cache,
                        # which is the per-token cache-traffic
                        # reduction
                        from ...ops.pallas import \
                            flash_decode_attention
                        ctx = flash_decode_attention(
                            q, ck, cv, positions, heads=self.heads,
                            scale=scale)
                    else:
                        ctx = self._attend_rows(q * scale, ck, cv,
                                                bias)
                x = self._attn_out(params, i, x, ctx)
                x = self._ffn_block(params, i, x, ad)
        return cache, self._head(params, x)

    def full_forward(self, params, tokens, ad=None):
        import jax.numpy as jnp
        T = tokens.shape[1]
        logits, _ = self._full_pass(
            params, tokens,
            jnp.full((tokens.shape[0],), T, 'int32'), ad)
        return logits

    # -- paged cache paths (docs/SERVING.md "Paged KV cache") ---------------

    supports_paging = True

    def paged_spec(self, page_size):
        """Pool metadata: one (pages, page_size, units) pool per layer
        K and V entry."""
        return PagedCacheSpec(
            {'l%d_%s' % (i, kv): ((self.units,), 'float32')
             for i in range(self.layers) for kv in ('k', 'v')},
            page_size, self.max_len)

    def paged_prefill(self, params, pool, tokens, length, page_ids,
                      ad=None):
        """Prefill landing through the page table: same `_full_pass`
        contractions as the slot prefill (identical reduction tree ->
        identical logits bits), with the computed K/V prefix scattered
        page by page to the host-allocated ``page_ids`` instead of one
        slot row. Trailing all-padding pages point at the trash page.
        """
        import jax.numpy as jnp
        S = tokens.shape[1]
        logits, kvs = self._full_pass(params, tokens, length, ad)
        npages = page_ids.shape[0]
        ps = pool[next(iter(pool))].shape[1]
        pad = npages * ps - S
        pool = dict(pool)
        for i, (k, v) in enumerate(kvs):
            for name, arr in (('k', k), ('v', v)):
                full = jnp.pad(arr[0], ((0, pad), (0, 0)))
                pool['l%d_%s' % (i, name)] = write_prefill_pages(
                    pool['l%d_%s' % (i, name)], full, page_ids)
        sel = (jnp.arange(S) == length - 1).astype(logits.dtype)
        return pool, jnp.einsum('s,sv->v', sel, logits[0])

    def paged_step(self, params, pool, tokens, positions, tables,
                   ad=None):
        """One decode step over the page pool: identical math to
        :meth:`step` except that the row write is addressed
        ``(table[pos // ps], pos % ps)`` and the history is read
        through the page table. Placed on a TPU the attention walks
        the table inside one kernel and reads a slot's live pages from
        the pool (``paged.walks_pages``): its reduction tree is the
        kernel's own, so the stream equals the slot cache's to
        rounding there (docs/DIVERGENCES.md). Anywhere else the
        per-slot K/V view is a gather of the slot's table entries;
        gathered rows beyond a slot's position (incl. trash-page
        garbage) carry exactly 0.0 attention weight, so the paged
        token stream is bit-identical to the slot cache's (module
        docstring argument)."""
        import jax
        import jax.numpy as jnp
        ps = pool[next(iter(pool))].shape[1]
        x = self._embed(params, tokens, positions)        # (S, U)
        page_ids = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]
        offsets = positions % ps
        scale = 1.0 / float(onp.sqrt(self.units // self.heads))
        pool = dict(pool)
        walk = walks_pages(pool['l0_k'].shape, pool['l0_k'].dtype)
        if not walk:
            ar = jnp.arange(tables.shape[1] * ps)
            bias = jnp.where(ar[None, :] <= positions[:, None],
                             0.0, -1e9)[:, None, :]       # (S, 1, Lp)
        for i in range(self.layers):
            p = lambda n: params['l%d_%s' % (i, n)]       # noqa: E731
            with jax.named_scope('layer%d' % i):
                with jax.named_scope('attn'):
                    qkv = self._adapted(x, p('qkv_w'), p('qkv_b'),
                                        ad, 'l%d_qkv' % i)
                    q, k, v = jnp.split(qkv, 3, axis=-1)
                    pool['l%d_k' % i] = scatter_rows(
                        pool['l%d_k' % i], k, page_ids, offsets)
                    pool['l%d_v' % i] = scatter_rows(
                        pool['l%d_v' % i], v, page_ids, offsets)
                if walk:
                    from ...ops.pallas import \
                        flash_paged_decode_attention
                    with jax.named_scope('attn'):
                        ctx = flash_paged_decode_attention(
                            q, pool['l%d_k' % i], pool['l%d_v' % i],
                            tables, positions, heads=self.heads,
                            scale=scale)
                else:
                    ck = gather_pages(pool['l%d_k' % i], tables)
                    cv = gather_pages(pool['l%d_v' % i], tables)
                    with jax.named_scope('attn'):
                        ctx = self._attend_rows(q * scale, ck, cv,
                                                bias)
                x = self._attn_out(params, i, x, ctx)
                x = self._ffn_block(params, i, x, ad)
        return pool, self._head(params, x)

    def paged_verify(self, params, pool, tokens, positions, tables,
                     ad=None):
        """Speculative verify: ``tokens`` (slots, C) — the last
        accepted token plus the draft's proposals — advance every slot
        C positions in ONE call, emitting logits at each. Causal
        within the chunk, each slot masked to its own history.

        Spec-only path: the chunked contractions combine a different
        reduction tree than the one-token step, so its logits agree to
        float32 precision, not bitwise (greedy acceptance re-checks
        against the draft, and rejected rows are simply masked until
        overwritten — docs/DIVERGENCES.md)."""
        import jax
        import jax.numpy as jnp
        C = tokens.shape[1]
        ps = pool[next(iter(pool))].shape[1]
        qpos = positions[:, None] + jnp.arange(C)[None, :]  # (S, C)
        x = self._embed(params, tokens, qpos)               # (S, C, U)
        page_ids = jnp.take_along_axis(tables, qpos // ps, axis=1)
        offsets = qpos % ps
        lp = tables.shape[1] * ps
        ar = jnp.arange(lp)
        # query c of slot s sees key j iff j <= positions[s] + c
        bias = jnp.where(ar[None, None, :] <= qpos[:, :, None],
                         0.0, -1e9)                       # (S, C, Lp)
        scale = 1.0 / float(onp.sqrt(self.units // self.heads))
        pool = dict(pool)
        for i in range(self.layers):
            p = lambda n: params['l%d_%s' % (i, n)]       # noqa: E731
            with jax.named_scope('layer%d' % i):
                with jax.named_scope('attn'):
                    qkv = self._adapted(x, p('qkv_w'), p('qkv_b'),
                                        ad, 'l%d_qkv' % i)
                    q, k, v = jnp.split(qkv, 3, axis=-1)
                    pool['l%d_k' % i] = scatter_rows(
                        pool['l%d_k' % i], k, page_ids, offsets)
                    pool['l%d_v' % i] = scatter_rows(
                        pool['l%d_v' % i], v, page_ids, offsets)
                ck = gather_pages(pool['l%d_k' % i], tables)
                cv = gather_pages(pool['l%d_v' % i], tables)
                with jax.named_scope('attn'):
                    ctx = self._attend_view(q * scale, ck, cv, bias)
                x = self._attn_out(params, i, x, ctx)
                x = self._ffn_block(params, i, x, ad)
        return pool, self._head(params, x)              # (S, C, V)

    def init_params(self, seed=0):
        rs = onp.random.RandomState(seed)
        U, H = self.units, self.hidden

        def w(*shape):
            return (rs.randn(*shape) * 0.05).astype('float32')

        params = {'embed': w(self.vocab, U),
                  'pos': w(self.max_len, U),
                  'out_bias': onp.zeros(self.vocab, 'float32')}
        for i in range(self.layers):
            params.update({
                'l%d_qkv_w' % i: w(3 * U, U),
                'l%d_qkv_b' % i: onp.zeros(3 * U, 'float32'),
                'l%d_out_w' % i: w(U, U),
                'l%d_out_b' % i: onp.zeros(U, 'float32'),
                'l%d_ln1_g' % i: onp.ones(U, 'float32'),
                'l%d_ln1_b' % i: onp.zeros(U, 'float32'),
                'l%d_ffn1_w' % i: w(H, U),
                'l%d_ffn1_b' % i: onp.zeros(H, 'float32'),
                'l%d_ffn2_w' % i: w(U, H),
                'l%d_ffn2_b' % i: onp.zeros(U, 'float32'),
                'l%d_ln2_g' % i: onp.ones(U, 'float32'),
                'l%d_ln2_b' % i: onp.zeros(U, 'float32'),
            })
        return params


# ---------------------------------------------------------------------------
# construction helpers
# ---------------------------------------------------------------------------

_FAMILIES = {RNNLM.family: RNNLM, TransformerLM.family: TransformerLM}


def model_from_config(family, config):
    """Factory the frozen-artifact loader dispatches through."""
    from . import cohere2  # noqa: F401  (registers its family)
    if family == 'xing4_0':
        # not imported with the package: a process that serves another
        # family pays nothing for it
        from . import xing4  # noqa: F401  (registers its family)
    cls = _FAMILIES.get(family)
    if cls is None:
        raise ValueError('unknown decode family %r (have %s)'
                         % (family, sorted(_FAMILIES)))
    return cls(config)


def init_rnn_lm(vocab, embed=32, hidden=64, layers=1, mode='lstm',
                max_len=128, seed=0):
    """Deterministic small RNN LM (tests/bench): (model, params)."""
    model = RNNLM(dict(vocab=vocab, embed=embed, hidden=hidden,
                       layers=layers, mode=mode, max_len=max_len))
    return model, model.init_params(seed)


def init_transformer_lm(vocab, units=32, hidden=64, layers=2, heads=4,
                        max_len=64, seed=0):
    """Deterministic small causal transformer LM: (model, params)."""
    model = TransformerLM(dict(vocab=vocab, units=units, hidden=hidden,
                               layers=layers, heads=heads,
                               max_len=max_len))
    return model, model.init_params(seed)


def from_gluon_rnn_lm(embedding, rnn, decoder, max_len=128):
    """Adapt a trained gluon RNN language model — ``Embedding`` ->
    ``rnn.LSTM/GRU/RNN`` (``gluon/rnn/rnn_layer.py``) -> ``Dense``
    head — into (RNNLM, params).

    The flat RNN parameter vector is rebuilt in the exact
    ``_RNNLayer._flat_params`` order (weights for all layers, then
    biases), so the decode cell consumes the same cuDNN-layout slices
    the fused training op does.
    """
    if getattr(rnn, '_dir', 1) != 1:
        raise ValueError('autoregressive decode needs a unidirectional '
                         'RNN (got bidirectional)')
    mode = rnn._mode
    layers = rnn._num_layers
    hidden = rnn._hidden_size
    embed_w = _as_numpy(embedding.weight.data())
    vocab, embed_dim = embed_w.shape
    pieces = []
    for group in (('i2h_weight', 'h2h_weight'), ('i2h_bias',
                                                 'h2h_bias')):
        for layer in range(layers):
            for piece in group:
                arr = _as_numpy(
                    getattr(rnn, 'l%d_%s' % (layer, piece)).data())
                pieces.append(arr.reshape(-1))
    out_w = _as_numpy(decoder.weight.data())
    out_b = _as_numpy(decoder.bias.data()) if decoder.bias is not None \
        else onp.zeros(out_w.shape[0], 'float32')
    if out_w.shape != (vocab, hidden):
        raise ValueError('decoder weight %r does not map hidden %d -> '
                         'vocab %d' % (out_w.shape, hidden, vocab))
    model = RNNLM(dict(vocab=vocab, embed=embed_dim, hidden=hidden,
                       layers=layers, mode=mode, max_len=max_len))
    params = {'embed_weight': embed_w.astype('float32'),
              'rnn_params': onp.concatenate(pieces).astype('float32'),
              'out_weight': out_w.astype('float32'),
              'out_bias': out_b.astype('float32')}
    return model, params
