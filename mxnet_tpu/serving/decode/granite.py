"""Decode family ``granitemoehybrid``: Mamba-2 layers with a recurrent
state a sequence beside grouped-attention layers with paged K/V, every
layer followed by routed experts and a shared MLP.

One block, ``x`` the float32 residual stream, ``m`` the
``residual_multiplier``::

    h0     = embedding_multiplier * E[token]
    u      = x + m * mixer(RMSNorm_1(x))
    y      = u + m * (routed(n) + shared(n)),        n = RMSNorm_2(u)
    logits = RMSNorm_f(y_last) E^T / logits_scaling  (tied embedding)

    mamba mixer, input r:
      [z | xBC | dt] = r W_in                  I | I + 2N | heads, no bias
      xBC_t  = silu(b_c + sum_{j<K} w_c[:, j] * xBC_{t-K+1+j})
                                               depthwise, causal, zeros
                                               before the prompt
      [x | B | C] = xBC_t                      I = heads x P | N | N
      D_t,h  = softplus(dt_t,h + dt_bias_h);  a_t,h = exp(D_t,h * A_h),
               A_h = -exp(A_log_h)
      S_t[h,p,n] = a_t,h * S_{t-1}[h,p,n] + D_t,h * x_t[h,p] * B_t[n]
      y_t[h,p]   = sum_n S_t[h,p,n] * C_t[n] + Dskip_h * x_t[h,p]
      out    = RMSNorm_g(y_t * silu(z_t)) W_out       (over all I channels)
    attention mixer: q = r Wq, k, v = r Wk, r Wv (grouped heads), no
      positional term, softmax(attention_multiplier * q k^T + causal) v Wo
    routed = sum_{e in T, e held here} g_e * W2_e(silu(W1_e n) * W3_e n)
      T = the top_k largest of the router's logits n Wr; g = softmax over
      those top_k logits, held here or not
    shared = S2(silu(S1 n) * S3 n)

**The cache.** An attention layer's K and V are paged
(``paged.PagedCacheSpec.entries``); a Mamba layer's state is constant a
sequence, the carried ``S`` (heads, P, N) in float32 and the
convolution's last ``K - 1`` inputs, and lives in slot entries of the
same donated pytree (``slot_entries``): the step updates the rows of the
slots that step (an idle slot has ``dt`` 0, so ``a`` is 1 and nothing is
added, and its convolution does not shift), a prefill rewrites its
slot's rows whole. A prefill padded to its bucket leaves the state of
position ``length - 1``: ``dt`` is 0 at and past ``length`` and the
convolution's state is cut from the last real positions. The prefill's
recurrence runs in chunks of ``mamba_chunk`` positions (the state-space
duality form: within a chunk a masked, decay-weighted product, between
chunks the recurrence on ``S``), plain ``jax.numpy``.

**The chip's share**: told which experts it holds (``held_experts``), it
routes over all ``experts`` logits, normalises over all ``top_k``
selected, and adds only what its own experts give (``blocks.py``).

**Precision.** Parameters, K/V and the convolution's state in ``dtype``
(bfloat16 as served), matrix products with ``dtype`` operands and
float32 accumulation; RMSNorms, softmaxes, softplus, ``exp``, the
residual stream and the carried state ``S`` in float32, and every
product that reads ``S`` at the highest precision. The router's product
and softmax are float32 at the highest precision.

Implemented: ``full_forward``, ``paged_prefill``, ``paged_step``. The
slot cache (``cache_spec`` / ``prefill`` / ``step``), ``paged_verify``
and ``lora_targets`` raise :class:`FamilyUnsupported`.
"""
from __future__ import annotations

import re

import numpy as onp

from . import blocks
from .model import _FAMILIES, DecodeModel, FamilyUnsupported
from .paged import (PagedCacheSpec, gather_pages, scatter_pages,
                    scatter_rows, walks_pages)

__all__ = ['GraniteHybridLM', 'init_granite_hybrid_lm']

MAMBA, ATTENTION = 'mamba', 'attention'


class GraniteHybridLM(DecodeModel):
    """config: vocab, max_len, hidden, layer_types (``mamba`` /
    ``attention`` per layer), eps, head_dim, heads, kv_heads (attention),
    mamba_heads, mamba_head_dim, mamba_state, mamba_conv, mamba_chunk,
    experts (the router's width), held_experts (ids held here), top_k,
    expert_hidden, shared_hidden, embedding_multiplier,
    residual_multiplier, attention_multiplier, logits_scaling, dtype;
    optional ``prefill_block`` (queries a block of prefill attention,
    512).

    params: embed (V, H), lnf_g (H,), and per layer ``l{i}_``: ln1_g,
    ln2_g (H,), router_w (H, experts), w1 / w3 (held, H, F), w2 (held,
    F, H), s1 / s3 (H, Fs), s2 (Fs, H); a Mamba layer: in_w (H, 2 I + 2
    N + heads), conv_w (I + 2 N, K), conv_b (I + 2 N,), dt_bias, A_log,
    D (heads,) float32, norm_g (I,), out_w (I, H); an attention layer:
    q_w (H, heads * d), k_w / v_w (H, kv_heads * d), o_w (heads * d, H).
    """

    family = 'granitemoehybrid'
    supports_paging = True
    # device-side counts a step returns beside its logits
    step_stats = ('moe_assignments', 'moe_assignments_here',
                  'moe_expert_load_max')
    # host-side counts of a prefill, by its bucket (prefill_counts)
    prefill_stats = ('ssm_prefill_chunks',)

    def __init__(self, config):
        config = dict(config)
        config.setdefault('dtype', 'bfloat16')
        config.setdefault('prefill_block', 512)
        config['layer_types'] = list(config['layer_types'])
        config['held_experts'] = [int(e) for e in config['held_experts']]
        super().__init__(config)
        self.hidden = int(config['hidden'])
        self.layer_types = config['layer_types']
        self.layers = len(self.layer_types)
        self.eps = float(config['eps'])
        self.head_dim = int(config['head_dim'])
        self.heads = int(config['heads'])
        self.kv_heads = int(config['kv_heads'])
        self.m_heads = int(config['mamba_heads'])
        self.m_dim = int(config['mamba_head_dim'])
        self.m_state = int(config['mamba_state'])
        self.m_conv = int(config['mamba_conv'])
        self.m_chunk = int(config['mamba_chunk'])
        self.inner = self.m_heads * self.m_dim
        self.conv_width = self.inner + 2 * self.m_state
        self.experts = int(config['experts'])
        self.held = config['held_experts']
        self.top_k = int(config['top_k'])
        self.expert_hidden = int(config['expert_hidden'])
        self.shared_hidden = int(config['shared_hidden'])
        self.emb_mult = float(config['embedding_multiplier'])
        self.res_mult = float(config['residual_multiplier'])
        self.attn_mult = float(config['attention_multiplier'])
        self.logits_scaling = float(config['logits_scaling'])
        self.dtype = str(config['dtype'])
        self.prefill_block = int(config['prefill_block'])
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad:
            raise ValueError('unknown layer types %r' % sorted(bad))
        if ATTENTION not in self.layer_types:
            raise ValueError('no attention layer: a model of recurrent '
                             'state alone has nothing to page')
        if self.heads % self.kv_heads:
            raise ValueError('heads %d not divisible by kv_heads %d'
                             % (self.heads, self.kv_heads))
        if int(config.get('mamba_groups', 1)) != 1:
            raise ValueError('one group of B and C is implemented '
                             '(mamba_groups 1)')
        self._experts = blocks.HeldExperts(
            self.experts, self.held, self.top_k, self.hidden, self.dtype)
        self._mamba_layers = self.layer_types.count(MAMBA)

    # -- what this family does not implement --------------------------------

    def cache_spec(self):
        raise FamilyUnsupported(
            self.family, 'the slot cache (cache_spec / prefill / step): '
            'its attention layers keep pages, which only the paged '
            'cache manager holds beside the recurrent state; freeze it '
            'paged')

    def prefill(self, params, cache, tokens, length, slot):
        self.cache_spec()

    def step(self, params, cache, tokens, positions):
        self.cache_spec()

    def paged_verify(self, params, pool, tokens, positions, tables,
                     ad=None):
        raise FamilyUnsupported(
            self.family, 'paged_verify (speculative decoding): a '
            'rejected token has already advanced the recurrent state, '
            'and no snapshot of it is kept to go back to')

    def lora_targets(self):
        raise FamilyUnsupported(
            self.family, 'lora_targets (low-rank adapters): no adapter '
            'layout is defined for the fused Mamba projection or for '
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

    def _route(self, p, n):
        """Logits over all experts (float32, highest precision), the
        ``top_k`` largest and a softmax over those: (weights (T, K)
        float32, expert ids (T, K))."""
        import jax
        import jax.numpy as jnp
        logits = jnp.einsum('th,he->te', n.astype('float32'),
                            p('router_w').astype('float32'),
                            precision=jax.lax.Precision.HIGHEST)
        top, top_i = jax.lax.top_k(logits, self.top_k)
        return blocks.softmax(top), top_i

    def _shared(self, p, n):
        import jax
        with jax.named_scope('shared'):
            return blocks.gated_ffn('th,hf->tf', 'tf,fh->th', n, p('s1'),
                                    p('s3'), p('s2'), self.dtype)

    def _in_proj(self, p, r):
        """The fused input projection of a Mamba layer: z (T, I)
        float32, xBC (T, I + 2 N) in ``dtype`` (what the convolution
        and its state read), dt (T, heads) float32 before its bias."""
        zxbcdt = self._mm('th,ho->to', r, p('in_w'))
        z = zxbcdt[:, :self.inner]
        xbc = zxbcdt[:, self.inner:self.inner + self.conv_width]
        return z, xbc.astype(self.dtype), zxbcdt[:, -self.m_heads:]

    def _split(self, xbc):
        """The convolution's output (T, I + 2 N) as x (T, heads, P), B
        and C (T, N)."""
        t = xbc.shape[0]
        return (xbc[:, :self.inner].reshape(t, self.m_heads, self.m_dim),
                xbc[:, self.inner:self.inner + self.m_state],
                xbc[:, self.inner + self.m_state:])

    def _dt(self, p, dt_raw, real):
        """softplus(dt + bias), and 0 for the rows that are no token
        (padding, an idle slot): ``a`` is then 1 and nothing is added,
        so the state passes unchanged."""
        import jax
        import jax.numpy as jnp
        dt = jax.nn.softplus(dt_raw + p('dt_bias').astype('float32'))
        return jnp.where(real[:, None], dt, 0.0)

    def _mixer_out(self, p, y, xh, z):
        """Skip, gate, the gated norm over all channels, and the output
        projection: y, xh (T, heads, P), z (T, I) -> (T, H)."""
        import jax
        y = y + p('D').astype('float32')[None, :, None] * xh
        gated = y.reshape(y.shape[0], self.inner) * jax.nn.silu(z)
        return self._mm('ti,ih->th', self._rms(gated, p('norm_g')),
                        p('out_w'))

    def _scan_chunks(self, xh, dt, b, c, a_log):
        """The recurrence over one whole sequence from a zero state, in
        chunks of ``mamba_chunk``: xh (S, heads, P), dt (S, heads)
        float32 and already 0 where there is no token, b / c (S, N).
        Returns (y (S, heads, P) float32 without the skip, the state
        after the last position (heads, P, N) float32)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        s, q = xh.shape[0], self.m_chunk
        nq = -(-s // q)
        pad = nq * q - s
        a = -jnp.exp(a_log.astype('float32'))                # (heads,)
        chunks = tuple(
            jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
                (nq, q) + v.shape[1:])
            for v in (xh.astype(self.dtype), dt, b.astype(self.dtype),
                      c.astype(self.dtype)))
        causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
        hi = jax.lax.Precision.HIGHEST

        def one_chunk(state, chunk):
            xq, dtq, bq, cq = chunk
            cs = jnp.cumsum(dtq * a[None], axis=0)           # (Q, heads)
            # within the chunk: position i reads position j <= i through
            # the decay between them
            seg = jnp.where(causal[:, :, None],
                            cs[:, None, :] - cs[None, :, :], -jnp.inf)
            weight = self._mm('in,jn->ij', cq, bq)[:, :, None] \
                * jnp.exp(seg) * dtq[None, :, :]             # (Qi, Qj, heads)
            y = self._mm('ijh,jhp->ihp', weight, xq)
            # what the chunks before left in the state
            y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
                'in,hpn->ihp', cq.astype('float32'), state, precision=hi)
            # the state this chunk leaves
            to_end = jnp.exp(cs[-1][None] - cs) * dtq        # (Q, heads)
            added = self._mm('jhp,jn->hpn',
                             to_end[:, :, None] * xq.astype('float32'),
                             bq)
            return jnp.exp(cs[-1])[:, None, None] * state + added, y

        state, y = lax.scan(
            one_chunk,
            jnp.zeros((self.m_heads, self.m_dim, self.m_state), 'float32'),
            chunks)
        return y.reshape((nq * q,) + y.shape[2:])[:s], state

    def _mamba_sequence(self, p, r, length):
        """A Mamba mixer over one whole sequence r (S, H) of which
        ``length`` positions are real: (out (S, H), state after
        position ``length - 1`` (heads, P, N) float32, the
        convolution's last K - 1 inputs (K - 1, I + 2 N))."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        s, k = r.shape[0], self.m_conv
        z, xbc, dt_raw = self._in_proj(p, r)
        with jax.named_scope('conv'):
            padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
            w = p('conv_w').astype('float32')
            conv = sum(padded[j:j + s].astype('float32') * w[None, :, j]
                       for j in range(k))
            conv = jax.nn.silu(conv + p('conv_b').astype('float32'))
            last = lax.dynamic_slice_in_dim(padded, length, k - 1, 0)
        with jax.named_scope('ssm_scan'):
            xh, b, c = self._split(conv)
            dt = self._dt(p, dt_raw, jnp.arange(s) < length)
            y, state = self._scan_chunks(xh, dt, b, c, p('A_log'))
        return self._mixer_out(p, y, xh, z), state, last

    def _mamba_step(self, p, r, live, state, conv_state):
        """A Mamba mixer over one token a slot, r (slots, H): (out
        (slots, H), state', conv_state'); the rows of slots that are
        not ``live`` pass unchanged."""
        import jax
        import jax.numpy as jnp
        z, xbc, dt_raw = self._in_proj(p, r)
        with jax.named_scope('conv'):
            window = jnp.concatenate([conv_state, xbc[:, None]], axis=1)
            conv = jnp.sum(window.astype('float32')
                           * p('conv_w').astype('float32').T[None], axis=1)
            conv = jax.nn.silu(conv + p('conv_b').astype('float32'))
            conv_state = jnp.where(live[:, None, None], window[:, 1:],
                                   conv_state)
        with jax.named_scope('ssm_update'):
            xh, b, c = self._split(conv)
            dt = self._dt(p, dt_raw, live)
            decay = jnp.exp(
                dt * -jnp.exp(p('A_log').astype('float32'))[None])
            state = decay[:, :, None, None] * state \
                + (dt[:, :, None] * xh)[..., None] * b[:, None, None, :]
            y = jnp.sum(state * c[:, None, None, :], axis=-1)
        return self._mixer_out(p, y, xh, z), state, conv_state

    def _qkv(self, p, r):
        """q (T, kv_heads, group, d) scaled by the attention
        multiplier, k and v (T, kv_heads, d), all in ``dtype``; no
        positional term."""
        t, d = r.shape[0], self.head_dim
        q = self._mm('th,ho->to', r, p('q_w')) * self.attn_mult
        k = self._mm('th,ho->to', r, p('k_w'))
        v = self._mm('th,ho->to', r, p('v_w'))
        return (q.reshape(t, self.kv_heads, self.heads // self.kv_heads,
                          d).astype(self.dtype),
                k.reshape(t, self.kv_heads, d).astype(self.dtype),
                v.reshape(t, self.kv_heads, d).astype(self.dtype))

    def _embed(self, params, tokens):
        import jax
        import jax.numpy as jnp
        with jax.named_scope('embed'):
            return self.emb_mult * jnp.take(
                params['embed'], tokens, axis=0).astype('float32')

    def _head(self, params, x):
        import jax
        with jax.named_scope('lm_head'):
            return self._mm('...h,vh->...v',
                            self._rms(x, params['lnf_g']),
                            params['embed']) * (1.0 / self.logits_scaling)

    def _experts_block(self, p, u, layer, rows):
        """The second half of a layer: ``u + m * (routed + shared)``.
        ``layer`` is the step's or the prefill's expert layer
        (``HeldExperts.dense`` / ``grouped``) and ``rows`` what it
        takes after the gates: the live slots, or the prompt's length.
        Returns (y, counts)."""
        import jax
        with jax.named_scope('moe'):
            n = self._rms(u, p('ln2_g'))
            with jax.named_scope('router'):
                w, top_i = self._route(p, n)
            out, counts = layer(n, w, top_i, rows, p('w1'), p('w3'),
                                p('w2'))
            return u + self.res_mult * (out + self._shared(p, n)), counts

    def _sequence_pass(self, params, tokens, length):
        """One whole sequence, tokens (S,): the hidden states before
        the final norm (S, H) and what each layer leaves in the cache:
        an attention layer its (k, v) (S, kv_heads * d), a Mamba layer
        its (state, convolution inputs) after position ``length - 1``.
        Rows at or past ``length`` are padding: causal attention keeps
        them from every real row, they leave the recurrent state as it
        was and the router sends them nowhere. The prefill AND the
        uncached reference pass."""
        import jax
        s = tokens.shape[0]
        x = self._embed(params, tokens)
        left = []
        for i, kind in enumerate(self.layer_types):
            p = lambda name: params['l%d_%s' % (i, name)]  # noqa: E731
            with jax.named_scope('layer%d' % i):
                r = self._rms(x, p('ln1_g'))
                if kind == MAMBA:
                    with jax.named_scope('mamba'):
                        mixed, state, last = self._mamba_sequence(
                            p, r, length)
                        left.append((state, last))
                else:
                    with jax.named_scope('attn'):
                        q, k, v = self._qkv(p, r)
                        left.append((k.reshape(s, -1), v.reshape(s, -1)))
                        mixed = self._mm(
                            'to,oh->th',
                            blocks.attend_blocks(q, k, v,
                                                 self.prefill_block, None,
                                                 self.dtype), p('o_w'))
                x, _counts = self._experts_block(
                    p, x + self.res_mult * mixed, self._experts.grouped,
                    length)
        return x, left

    def full_forward(self, params, tokens):
        """tokens (B, T) -> logits (B, T, V), no cache."""
        import jax.numpy as jnp
        t = tokens.shape[1]
        return jnp.stack([
            self._head(params, self._sequence_pass(params, row, t)[0])
            for row in tokens])

    # -- paged cache paths ---------------------------------------------------

    def paged_spec(self, page_size):
        """An attention layer's K and V are paged, one (pages,
        page_size, kv_heads * d) pool each; a Mamba layer's carried
        state (heads, P, N) float32 and its convolution's last K - 1
        inputs are slot entries (paged.PagedCacheSpec)."""
        row = (self.kv_heads * self.head_dim,)
        paged, slot = {}, {}
        for i, kind in enumerate(self.layer_types):
            if kind == MAMBA:
                slot['l%d_ssm' % i] = (
                    (self.m_heads, self.m_dim, self.m_state), 'float32')
                slot['l%d_conv' % i] = (
                    (self.m_conv - 1, self.conv_width), self.dtype)
            else:
                paged['l%d_k' % i] = (row, self.dtype)
                paged['l%d_v' % i] = (row, self.dtype)
        return PagedCacheSpec(paged, page_size, self.max_len,
                              slot_entries=slot)

    def prefill_counts(self, bucket):
        """What one prefill of ``bucket`` positions counts on the
        host: the chunks its recurrences ran, over all Mamba layers."""
        return {'ssm_prefill_chunks':
                self._mamba_layers * -(-int(bucket) // self.m_chunk)}

    def paged_prefill(self, params, pool, tokens, length, page_ids,
                      ad=None):
        """Prefill through the page tables: tokens (1, S); the K and V
        of an attention layer land in ``page_ids['full']``, a Mamba
        layer's state and convolution inputs after position ``length -
        1`` overwrite row ``page_ids['slot']`` of its slot entries.
        Returns (pool', logits (V,) at position ``length - 1``)."""
        import jax.numpy as jnp
        from jax import lax
        del ad
        ids, slot = page_ids['full'], page_ids['slot']
        s = tokens.shape[1]
        x, left = self._sequence_pass(params, tokens[0], length)
        pool = dict(pool)
        for i, kind in enumerate(self.layer_types):
            if kind == MAMBA:
                for name, arr in zip(('ssm', 'conv'), left[i]):
                    key = 'l%d_%s' % (i, name)
                    pool[key] = lax.dynamic_update_index_in_dim(
                        pool[key], arr.astype(pool[key].dtype), slot, 0)
                continue
            for name, arr in zip('kv', left[i]):
                key = 'l%d_%s' % (i, name)
                pad = ids.shape[0] * pool[key].shape[1] - s
                pool[key] = scatter_pages(
                    pool[key], jnp.pad(arr, ((0, pad), (0, 0))), ids)
        last = lax.dynamic_slice_in_dim(x, length - 1, 1, 0)[0]
        return pool, self._head(params, last)

    def paged_step(self, params, pool, tokens, positions, tables,
                   ad=None):
        """One decode step: tokens / positions (slots,). An attention
        layer appends to and gathers ``tables`` (slots, max_pages); a
        Mamba layer updates its slot entries in place. A slot is live
        iff its position is above 0 (a sequence's first step comes
        after at least one prompt token). Returns (pool', logits
        (slots, V), counts (3,) int32 in ``step_stats``' order: live
        slots x top_k x layers, the assignments among them that landed
        on a held expert, and the largest count one held expert of one
        layer saw)."""
        import jax
        import jax.numpy as jnp
        del ad
        live = positions > 0
        x = self._embed(params, tokens)
        pool = dict(pool)
        here = jnp.zeros((), 'int32')
        load = jnp.zeros((), 'int32')
        for i, kind in enumerate(self.layer_types):
            p = lambda name: params['l%d_%s' % (i, name)]  # noqa: E731
            with jax.named_scope('layer%d' % i):
                r = self._rms(x, p('ln1_g'))
                if kind == MAMBA:
                    sk, ck = 'l%d_ssm' % i, 'l%d_conv' % i
                    with jax.named_scope('mamba'):
                        mixed, pool[sk], pool[ck] = self._mamba_step(
                            p, r, live, pool[sk], pool[ck])
                else:
                    mixed = self._attention_step(p, r, pool, i, positions,
                                                 tables)
                x, counts = self._experts_block(
                    p, x + self.res_mult * mixed, self._experts.dense,
                    live)
                here = here + jnp.sum(counts)
                load = jnp.maximum(load, jnp.max(counts))
        routed = jnp.sum(live).astype('int32') * (self.top_k * self.layers)
        return pool, self._head(params, x), jnp.stack([routed, here, load])

    def _attention_step(self, p, r, pool, i, positions, tables):
        """An attention layer's mixer in the step: append this token's
        K and V (``pool`` is updated in place, a dict) and attend over
        what each row's position has seen: placed on a TPU one kernel
        walks the table and reads the live pages
        (``paged.walks_pages``), anywhere else the table is gathered
        into a view."""
        import jax
        import jax.numpy as jnp
        kk, vk = 'l%d_k' % i, 'l%d_v' % i
        ps = pool[kk].shape[1]
        at = jnp.take_along_axis(tables, (positions // ps)[:, None],
                                 axis=1)[:, 0]
        offsets = positions % ps
        with jax.named_scope('attn'):
            q, k, v = self._qkv(p, r)
            pool[kk] = scatter_rows(pool[kk], k.reshape(k.shape[0], -1),
                                    at, offsets)
            pool[vk] = scatter_rows(pool[vk], v.reshape(v.shape[0], -1),
                                    at, offsets)
        if walks_pages(pool[kk].shape, pool[kk].dtype):
            from ...ops.pallas import flash_paged_decode_attention
            with jax.named_scope('attn'):
                # q carries the attention multiplier already
                ctx = flash_paged_decode_attention(
                    q.reshape(q.shape[0], -1), pool[kk], pool[vk], tables,
                    positions, heads=self.heads, scale=1.0)
                return self._mm('to,oh->th', ctx, p('o_w'))
        keys = gather_pages(pool[kk], tables)
        values = gather_pages(pool[vk], tables)
        with jax.named_scope('attn'):
            seen = jnp.arange(tables.shape[1] * ps)[None] \
                <= positions[:, None]
            return self._mm(
                'to,oh->th',
                blocks.attend_rows(q, keys, values, seen, self.dtype),
                p('o_w'))

    # -- construction --------------------------------------------------------

    def param_shapes(self):
        h, d = self.hidden, self.head_dim
        f, fs, eh = self.expert_hidden, self.shared_hidden, len(self.held)
        shapes = {'embed': (self.vocab, h), 'lnf_g': (h,)}
        for i, kind in enumerate(self.layer_types):
            shapes.update({
                'l%d_ln1_g' % i: (h,), 'l%d_ln2_g' % i: (h,),
                'l%d_router_w' % i: (h, self.experts),
                'l%d_w1' % i: (eh, h, f), 'l%d_w3' % i: (eh, h, f),
                'l%d_w2' % i: (eh, f, h),
                'l%d_s1' % i: (h, fs), 'l%d_s3' % i: (h, fs),
                'l%d_s2' % i: (fs, h)})
            if kind == MAMBA:
                shapes.update({
                    'l%d_in_w' % i: (h, self.inner + self.conv_width
                                     + self.m_heads),
                    'l%d_conv_w' % i: (self.conv_width, self.m_conv),
                    'l%d_conv_b' % i: (self.conv_width,),
                    'l%d_dt_bias' % i: (self.m_heads,),
                    'l%d_A_log' % i: (self.m_heads,),
                    'l%d_D' % i: (self.m_heads,),
                    'l%d_norm_g' % i: (self.inner,),
                    'l%d_out_w' % i: (self.inner, h)})
            else:
                shapes.update({
                    'l%d_q_w' % i: (h, self.heads * d),
                    'l%d_k_w' % i: (h, self.kv_heads * d),
                    'l%d_v_w' % i: (h, self.kv_heads * d),
                    'l%d_o_w' % i: (self.heads * d, h)})
        return shapes

    def init_params(self, seed=0):
        """Seeded leaves for tests (the benchmark makes its own):
        normal at 1/sqrt(fan-in), gains at 1, and Mamba-2's own for the
        recurrence: dt log-uniform in 1e-3..1e-1 through the inverse
        softplus, A uniform in 1..16, D ones, all float32."""
        import jax.numpy as jnp
        rs = onp.random.RandomState(seed)
        out = {}
        for name, shape in self.param_shapes().items():
            leaf = re.sub(r'^l\d+_', '', name)
            if leaf.endswith('_g'):
                out[name] = jnp.ones(shape, self.dtype)
            elif leaf == 'dt_bias':
                dt = onp.exp(rs.uniform(onp.log(1e-3), onp.log(1e-1),
                                        shape))
                out[name] = jnp.asarray(dt + onp.log(-onp.expm1(-dt)),
                                        'float32')
            elif leaf == 'A_log':
                out[name] = jnp.asarray(
                    onp.log(rs.uniform(1.0, 16.0, shape)), 'float32')
            elif leaf == 'D':
                out[name] = jnp.ones(shape, 'float32')
            elif leaf == 'conv_b':
                out[name] = jnp.asarray(0.1 * rs.randn(*shape), self.dtype)
            elif leaf == 'embed':
                out[name] = jnp.asarray(rs.randn(*shape), self.dtype)
            else:
                fan_in = shape[-1] if leaf == 'conv_w' else shape[-2]
                out[name] = jnp.asarray(
                    rs.randn(*shape) / onp.sqrt(fan_in), self.dtype)
        return out


_FAMILIES[GraniteHybridLM.family] = GraniteHybridLM


def init_granite_hybrid_lm(seed=0, **config):
    """Deterministic small model of the family: (model, params)."""
    small = dict(vocab=96, max_len=64, hidden=64,
                 layer_types=[MAMBA, MAMBA, ATTENTION, MAMBA], eps=1e-5,
                 head_dim=16, heads=4, kv_heads=2, mamba_heads=8,
                 mamba_head_dim=16, mamba_state=16, mamba_conv=4,
                 mamba_chunk=8, experts=8, held_experts=list(range(8)),
                 top_k=3, expert_hidden=32, shared_hidden=48,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 attention_multiplier=0.0625, logits_scaling=16.0,
                 dtype='float32')
    small.update(config)
    model = GraniteHybridLM(small)
    return model, model.init_params(seed)
