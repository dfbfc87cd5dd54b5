"""Plain reference for the ``cohere2_moe`` family (Command A+): one full
forward over a whole sequence, given this chip's share of each layer.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the configuration file's keys and the layer equations below; no cache,
no batching over requests, no kernels, nothing of the program. For layer
``l`` of kind ``layer_types[l]``, input ``x``::

    n      = (x - mean(x)) / sqrt(var(x) + eps) * g_l            (no bias)
    q,k,v  = n Wq, n Wk, n Wv      query head h reads KV head h // group
    sliding: q,k <- RoPE(q,k), interleaved pairs (2i, 2i+1), all of head_dim;
             key j visible to query t iff j <= t and t - j < sliding_window
    full:    no positional term; key j visible iff j <= t
    attn   = softmax(q k^T / sqrt(head_dim) + mask) v Wo
    s      = sigmoid(n Wr) over all published experts; T = the top-k largest
    w_e    = s_e / sum_{e in T} s_e
    routed = sum_{e in T and held here} w_e W2_e(silu(W1_e n) * W3_e n)
    shared = mean_j S2_j(silu(S1_j n) * S3_j n)
    y      = x + attn + routed + shared
    logits = logit_scale * LN_f(y) E^T           (E the tied embedding slice)

What the absent chips would add (their heads, their experts) is left out,
as in the program. ``dtype`` lowers every matrix product's operands (the
control); ``None`` is the reference. Weights stay bfloat16 on the device,
in the layout the program takes, and are raised to float32 one layer, one
expert at a time.

**Near ties are left out by rule.** ``T`` is discontinuous: where the k-th
and (k+1)-th scores of a position nearly tie, a program that differs from
these equations by rounding alone may pick the other expert, and one
expert's whole output is then swapped for another's. A position is *near
tied* if in some layer its k-th and (k+1)-th largest scores, as computed
here, lie within ``precision.router_tie_margin`` of each other and one of
the two experts is held here (a swap between two absent experts changes
nothing on this chip). The reference's own pass (``dtype`` None) answers a
near-tied position with a row of zeros: every token is then as good as the
best, so the comparison says nothing about it. The rule looks at the
reference's scores alone, never at what was served.
"""
import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from .common import cfg_key as _cfg_key
from .common import mm as _mm

SLIDING = 'sliding_attention'
# widths a sequence is padded to: few shapes, so few compiles (causal, so
# padding on the right changes nothing)
WIDTHS = (256, 2048, 4096, 8192)
QUERY_BLOCK = 1024


def sizes(cfg):
    """The sizes the equations need, from the configuration's keys."""
    return dict(
        hidden=cfg['hidden_size'], head_dim=cfg['head_dim'],
        heads=cfg['num_attention_heads'],
        kv_heads=cfg['num_key_value_heads'],
        ffn=cfg['intermediate_size'], held=len(cfg['held_experts']),
        experts=cfg['published']['num_experts'],
        shared=cfg['num_shared_experts'], vocab=cfg['vocab_size'])


def leaf_shapes(cfg):
    z = sizes(cfg)
    h, d, f = z['hidden'], z['head_dim'], z['ffn']
    shapes = {'embed': (z['vocab'], h), 'lnf_g': (h,)}
    for i in range(cfg['num_hidden_layers']):
        shapes.update({
            'l%d.ln_g' % i: (h,),
            'l%d.q_w' % i: (h, z['heads'] * d),
            'l%d.k_w' % i: (h, z['kv_heads'] * d),
            'l%d.v_w' % i: (h, z['kv_heads'] * d),
            'l%d.o_w' % i: (z['heads'] * d, h),
            'l%d.router_w' % i: (h, z['experts']),
            'l%d.w1' % i: (z['held'], h, f), 'l%d.w3' % i: (z['held'], h, f),
            'l%d.w2' % i: (z['held'], f, h),
            'l%d.s1' % i: (z['shared'], h, f),
            'l%d.s3' % i: (z['shared'], h, f),
            'l%d.s2' % i: (z['shared'], f, h)})
    return shapes


def leaf_std(cfg, name, shape):
    """Standard deviation of one leaf (the configuration's ``init`` block
    says why): inputs of a product at 1/sqrt(fan-in), so that what they
    give has unit scale; the three branch outputs at ``gain /
    sqrt(fan-in)``, so that each branch adds a stated amount to a residual
    stream of about unit scale; the embedding small beside them, at
    ``embed_std`` (tied to the head, a large one would make every
    position's best logit its own input token)."""
    init = cfg['init']
    leaf = name.split('.')[-1]
    if leaf == 'embed':
        return init['embed_std']
    fan_in = shape[-2]
    gain = {'o_w': init['attn_gain'], 's2': init['shared_gain'],
            'w2': init['routed_gain']}.get(leaf)
    if gain is not None:
        return gain / math.sqrt(fan_in)
    if leaf == 'q_w':
        return init['query_gain'] / math.sqrt(fan_in)
    return 1.0 / math.sqrt(fan_in)


def make_weights(cfg, seed):
    """Every leaf on the device in one jitted call from the seed, in the
    bfloat16 they are served in and the stacked layout the program takes."""
    shapes = leaf_shapes(cfg)

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith('_g'):
                w = 1.0 + cfg['init']['gain_std'] * jax.random.normal(
                    k, shape, 'float32')
            else:
                w = leaf_std(cfg, name, shape) * jax.random.normal(
                    k, shape, 'bfloat16').astype('float32')
            out[name] = w.astype('bfloat16')
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


def layer_norm(x, g, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g


def rope(x, theta):
    """x (S, heads, d): position t turns pair (2i, 2i+1) of every head by
    the angle t * theta ** (-2i / d)."""
    s, _, d = x.shape
    freq = theta ** (-np.arange(0, d, 2, dtype='float64') / d)
    ang = jnp.asarray(np.arange(s)[:, None] * freq[None, :], 'float32')
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def attention(q, k, v, window, dtype):
    """q (S, heads, d), k / v (S, kv_heads, d) -> (S, heads * d); ``window``
    None on a full layer. A block of queries at a time, each against every
    key under its mask, so that no (S, S) tensor is held."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)          # query head h reads h // group
    v = jnp.repeat(v, group, axis=1)
    keys = jnp.arange(s)[None, :]
    out = []
    for at in range(0, s, QUERY_BLOCK):
        qb = q[at:at + QUERY_BLOCK]
        t = at + jnp.arange(qb.shape[0])[:, None]
        seen = keys <= t
        if window is not None:
            seen = seen & (t - keys < window)
        sc = _mm(qb / math.sqrt(d), k, 'qhd,khd->hqk', dtype) \
            + jnp.where(seen, 0.0, -1e9)[None]
        out.append(_mm(jax.nn.softmax(sc, -1), v, 'hqk,khd->qhd', dtype))
    return jnp.concatenate(out, 0).reshape(s, heads * d)


def gated(n, w1, w3, w2, dtype):
    """W2(silu(W1 n) * W3 n) of one expert, its weights raised to float32."""
    w1, w3, w2 = (w.astype('float32') for w in (w1, w3, w2))
    h = jax.nn.silu(_mm(n, w1, 'sh,hf->sf', dtype)) \
        * _mm(n, w3, 'sh,hf->sf', dtype)
    return _mm(h, w2, 'sf,fh->sh', dtype)


@functools.partial(jax.jit, static_argnames=('cfg_key', 'held', 'kind',
                                             'tie', 'dtype'))
def layer(x, w, cfg_key, held, kind, tie, dtype):
    """One block over a whole sequence x (S, hidden); ``w`` the layer's
    leaves by their short names. Returns (y, near): the block's output and
    the positions (S,) whose selection nearly ties within ``tie``."""
    cfg = dict(cfg_key)
    s, d = x.shape[0], cfg['head_dim']
    n = layer_norm(x, w['ln_g'].astype('float32'), cfg['layer_norm_eps'])
    q, k, v = (_mm(n, w[m].astype('float32'), 'sh,ho->so', dtype)
               .reshape(s, -1, d) for m in ('q_w', 'k_w', 'v_w'))
    sliding = kind == SLIDING
    if sliding:
        q, k = rope(q, cfg['rope_theta']), rope(k, cfg['rope_theta'])
    attn = _mm(attention(q, k, v, cfg['sliding_window'] if sliding else None,
                         dtype),
               w['o_w'].astype('float32'), 'so,oh->sh', dtype)
    score = jax.nn.sigmoid(_mm(n, w['router_w'].astype('float32'),
                               'sh,he->se', dtype))
    k = cfg['num_experts_per_tok']
    ranked = jnp.argsort(-score, -1)
    last, nxt = ranked[:, k - 1], ranked[:, k]      # k-th, (k+1)-th largest
    at = jnp.arange(s)
    chosen = score >= score[at, last][:, None]      # the top-k largest
    here = jnp.zeros(score.shape[1], bool).at[jnp.asarray(held)].set(True)
    near = (score[at, last] - score[at, nxt] < tie) & (here[last] | here[nxt])
    weight = jnp.where(chosen, score, 0.0)
    weight = weight / jnp.sum(weight, -1, keepdims=True)

    def add_expert(acc, leaf):
        w1, w3, w2, we = leaf
        return acc + we[:, None] * gated(n, w1, w3, w2, dtype), None

    mine = weight[:, jnp.asarray(held)].T           # (held, S)
    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                             (w['w1'], w['w3'], w['w2'], mine))
    ones = jnp.ones((w['s1'].shape[0], s), 'float32')
    shared, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                             (w['s1'], w['s3'], w['s2'], ones))
    return x + attn + routed + shared / w['s1'].shape[0], near


@functools.partial(jax.jit, static_argnames=('eps', 'scale', 'dtype'))
def head(x, g, embed, eps, scale, dtype):
    n = layer_norm(x, g.astype('float32'), eps)
    return scale * _mm(n, embed.astype('float32'), 'nh,vh->nv', dtype)


def hidden(cfg, w, tokens, dtype=None):
    """One causal pass over ``tokens`` (S,): the hidden states before the
    final norm, (S, hidden), and the positions (S,) that nearly tie in some
    layer."""
    x = w['embed'][jnp.asarray(tokens)].astype('float32')
    key, held = _cfg_key(cfg), tuple(cfg['held_experts'])
    tie = float(cfg['precision']['router_tie_margin'])
    near = jnp.zeros(x.shape[0], bool)
    for i, kind in enumerate(cfg['layer_types']):
        lw = {k.split('.', 1)[1]: v for k, v in w.items()
              if k.startswith('l%d.' % i)}
        x, tied = layer(x, lw, key, held, kind, tie, dtype)
        near = near | tied
    return x, near


def next_token_logits(cfg, weights, prompts, outputs, dtype=None):
    """For each request, the logits that chose each served token: one
    teacher-forced pass over prompt + served tokens, a request at a time,
    padded on the right to one of ``WIDTHS``. Returns a list of
    (len(output), V) float32 arrays; without ``dtype`` the rows of
    near-tied positions are zeros (the module's rule)."""
    dtype = None if dtype is None else jnp.dtype(dtype)
    out, left_out, rows_in_all = [], 0, 0
    with jax.default_matmul_precision('highest'):
        for p, o in zip(prompts, outputs):
            n = len(p) + len(o)
            width = next((w for w in WIDTHS if w >= n), -(-n // 1024) * 1024)
            toks = np.zeros((width,), 'int32')
            toks[:len(p)] = p
            toks[len(p):n] = o
            x, near = hidden(cfg, weights, toks, dtype)
            rows = np.arange(len(p) - 1, n - 1)
            pad = -len(rows) % 128           # few distinct shapes
            at = jnp.asarray(np.concatenate([rows, np.zeros(pad, 'int64')]),
                             'int32')
            got = head(x[at], weights['lnf_g'], weights['embed'],
                       cfg['layer_norm_eps'], float(cfg['logit_scale']),
                       dtype)
            got = np.asarray(got)[:len(rows)]
            if dtype is None:
                tied = np.asarray(near)[rows]
                got = np.where(tied[:, None], np.float32(0), got)
                left_out += int(tied.sum())
                rows_in_all += len(rows)
            out.append(got)
    if dtype is None:
        print('[reference] %d of %d positions near tied and left out'
              % (left_out, rows_in_all), file=sys.stderr, flush=True)
    return out
