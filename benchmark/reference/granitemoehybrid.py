"""Plain reference for the ``granitemoehybrid`` family (Granite 4.0-H): one
full forward over a whole sequence, given this chip's share of each layer.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the configuration file's keys and the equations below; the recurrence
is a sequential ``lax.scan`` over positions, with no chunks, no cache, no
batching over requests, no kernels, nothing of the program. ``x`` is the
residual stream, ``m`` = ``residual_multiplier``::

    h0     = embedding_multiplier * E[token]
    layer: u = x + m * mixer(RMSNorm_1(x))
           y = u + m * (routed(n) + shared(n)),           n = RMSNorm_2(u)
    mamba mixer, input r:
      [z | xBC | dt] = r W_in              I | I + 2 N | heads  (I = heads x P)
      xBC_t  = silu(b_c + sum_{j<K} w_c[:, j] * xBC_{t-K+1+j})   zeros before
                                                                 the prompt
      [x | B | C] = xBC_t
      D_t,h  = softplus(dt_t,h + dt_bias_h);  a_t,h = exp(D_t,h * A_h),
               A_h = -exp(A_log_h)
      S_t[h,p,n] = a_t,h * S_{t-1}[h,p,n] + D_t,h * x_t[h,p] * B_t[n],  S_-1 = 0
      y_t[h,p]   = sum_n S_t[h,p,n] * C_t[n] + Dskip_h * x_t[h,p]
      out    = RMSNorm_g(y_t * silu(z_t)) W_out       (over all I channels)
    attention mixer: q, k, v = r Wq, r Wk, r Wv; query head h reads KV head
      h // group; no positions; softmax(attention_multiplier * q k^T + causal)
      v Wo
    routed = sum_{e in T and held here} g_e W2_e(silu(W1_e n) * W3_e n)
      T = the top-k largest of the logits n Wr over all published experts,
      g = softmax over those k logits, held here or not
    shared = S2(silu(S1 n) * S3 n)
    logits = RMSNorm_f(y) E^T / logits_scaling            (E the tied table)

What the absent chip's experts would add is left out, as in the program.
``dtype`` lowers every matrix product's operands (the control); ``None`` is
the reference. Weights stay bfloat16 on the device, in the layout the program
takes, and are raised to float32 one layer, one expert at a time. The
program rounds ``xBC`` to bfloat16 before the convolution (it is what the
convolution's state keeps); the reference does not.

**Near ties are left out by rule** (PR 30's, on ranks k and k+1 of the
logits): a position is *near tied* if in some layer its k-th and (k+1)-th
largest router logits, as computed here, lie within
``precision.router_tie_margin`` of each other and one of the two experts is
held here. The reference's own pass (``dtype`` None) answers a near-tied
position with a row of zeros: every token is then as good as the best. The
rule looks at the reference's logits alone, never at what was served.
"""
import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from .common import cfg_key as _cfg_key
from .common import mm as _mm

MAMBA = 'mamba'
# widths a sequence is padded to: few shapes, so few compiles (causal, so
# padding on the right changes nothing)
WIDTHS = (256, 1024, 2048, 3072)
QUERY_BLOCK = 1024
# leaves kept in float32: the recurrence's own parameters
FLOAT32_LEAVES = ('dt_bias', 'A_log', 'D')


def sizes(cfg):
    """The sizes the equations need, from the configuration's keys."""
    heads, p = cfg['mamba_n_heads'], cfg['mamba_d_head']
    return dict(
        hidden=cfg['hidden_size'], head_dim=cfg['head_dim'],
        heads=cfg['num_attention_heads'],
        kv_heads=cfg['num_key_value_heads'],
        ffn=cfg['intermediate_size'], shared=cfg['shared_intermediate_size'],
        held=len(cfg['held_experts']),
        experts=cfg['published']['num_local_experts'],
        vocab=cfg['vocab_size'], m_heads=heads, m_dim=p,
        inner=heads * p, state=cfg['mamba_d_state'], conv=cfg['mamba_d_conv'])


def leaf_shapes(cfg):
    z = sizes(cfg)
    h, d, f, fs = z['hidden'], z['head_dim'], z['ffn'], z['shared']
    width = z['inner'] + 2 * z['state']
    shapes = {'embed': (z['vocab'], h), 'lnf_g': (h,)}
    for i, kind in enumerate(cfg['layer_types']):
        shapes.update({
            'l%d.ln1_g' % i: (h,), 'l%d.ln2_g' % i: (h,),
            'l%d.router_w' % i: (h, z['experts']),
            'l%d.w1' % i: (z['held'], h, f), 'l%d.w3' % i: (z['held'], h, f),
            'l%d.w2' % i: (z['held'], f, h),
            'l%d.s1' % i: (h, fs), 'l%d.s3' % i: (h, fs),
            'l%d.s2' % i: (fs, h)})
        if kind == MAMBA:
            shapes.update({
                'l%d.in_w' % i: (h, z['inner'] + width + z['m_heads']),
                'l%d.conv_w' % i: (width, z['conv']),
                'l%d.conv_b' % i: (width,),
                'l%d.dt_bias' % i: (z['m_heads'],),
                'l%d.A_log' % i: (z['m_heads'],),
                'l%d.D' % i: (z['m_heads'],),
                'l%d.norm_g' % i: (z['inner'],),
                'l%d.out_w' % i: (z['inner'], h)})
        else:
            shapes.update({
                'l%d.q_w' % i: (h, z['heads'] * d),
                'l%d.k_w' % i: (h, z['kv_heads'] * d),
                'l%d.v_w' % i: (h, z['kv_heads'] * d),
                'l%d.o_w' % i: (z['heads'] * d, h)})
    return shapes


def leaf_std(cfg, name, shape):
    """Standard deviation of one normal leaf (the configuration's ``init``
    block says why): inputs of a product at 1/sqrt(fan-in), so that what
    they give has unit scale; the four branch outputs at ``gain /
    sqrt(fan-in)``; the query and the router at gains of their own; the
    embedding small beside the stream."""
    init = cfg['init']
    leaf = name.split('.')[-1]
    if leaf == 'embed':
        return init['embed_std']
    if leaf == 'conv_b':
        return init['conv_bias_std']
    fan_in = shape[-1] if leaf == 'conv_w' else shape[-2]
    gain = {'out_w': init['mixer_gain'], 'o_w': init['attn_gain'],
            's2': init['shared_gain'], 'w2': init['routed_gain'],
            'q_w': init['query_gain'],
            'router_w': init['router_gain']}.get(leaf, 1.0)
    return gain / math.sqrt(fan_in)


def make_weights(cfg, seed):
    """Every leaf on the device in one jitted call from the seed, in the
    bfloat16 they are served in and the stacked layout the program takes.
    The recurrence's own leaves are Mamba-2's: dt log-uniform in 1e-3..1e-1
    through the inverse softplus, A uniform in 1..16, D ones, float32."""
    shapes = leaf_shapes(cfg)

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            leaf = name.split('.')[-1]
            if leaf == 'dt_bias':
                dt = jnp.exp(jax.random.uniform(
                    k, shape, 'float32', math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif leaf == 'A_log':
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, 'float32', 1.0, 16.0))
            elif leaf == 'D':
                out[name] = jnp.ones(shape, 'float32')
            elif leaf.endswith('_g'):
                out[name] = (1.0 + cfg['init']['gain_std']
                             * jax.random.normal(k, shape, 'float32')
                             ).astype('bfloat16')
            else:
                out[name] = (leaf_std(cfg, name, shape) * jax.random.normal(
                    k, shape, 'bfloat16').astype('float32')
                             ).astype('bfloat16')
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def attention(q, k, v, scale, dtype):
    """q (S, heads, d), k / v (S, kv_heads, d) -> (S, heads * d), causal,
    no positions. A block of queries at a time, each against every key
    under its mask, so that no (S, S) tensor is held."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)          # query head h reads h // group
    v = jnp.repeat(v, group, axis=1)
    keys = jnp.arange(s)[None, :]
    out = []
    for at in range(0, s, QUERY_BLOCK):
        qb = q[at:at + QUERY_BLOCK]
        t = at + jnp.arange(qb.shape[0])[:, None]
        sc = _mm(qb * scale, k, 'qhd,khd->hqk', dtype) \
            + jnp.where(keys <= t, 0.0, -1e9)[None]
        out.append(_mm(jax.nn.softmax(sc, -1), v, 'hqk,khd->qhd', dtype))
    return jnp.concatenate(out, 0).reshape(s, heads * d)


def gated(n, w1, w3, w2, dtype):
    """W2(silu(W1 n) * W3 n) of one expert, its weights raised to float32."""
    w1, w3, w2 = (w.astype('float32') for w in (w1, w3, w2))
    h = jax.nn.silu(_mm(n, w1, 'sh,hf->sf', dtype)) \
        * _mm(n, w3, 'sh,hf->sf', dtype)
    return _mm(h, w2, 'sf,fh->sh', dtype)


def mamba(r, w, cfg, dtype):
    """The Mamba-2 mixer over a whole sequence r (S, hidden), one position
    after another from a zero state."""
    heads, p, n = cfg['mamba_n_heads'], cfg['mamba_d_head'], \
        cfg['mamba_d_state']
    inner, k = heads * p, cfg['mamba_d_conv']
    s = r.shape[0]
    zxbcdt = _mm(r, w['in_w'].astype('float32'), 'sh,ho->so', dtype)
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:-heads], \
        zxbcdt[:, -heads:]
    before = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], 0)
    conv_w = w['conv_w'].astype('float32')
    conv = sum(before[j:j + s] * conv_w[None, :, j] for j in range(k))
    xbc = jax.nn.silu(conv + w['conv_b'].astype('float32'))
    x = xbc[:, :inner].reshape(s, heads, p)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + w['dt_bias'])                   # (S, heads)
    decay = jnp.exp(dt * -jnp.exp(w['A_log'])[None])

    def one_position(state, at):
        a_t, dt_t, x_t, b_t, c_t = at
        state = a_t[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, _mm(state, c_t, 'hpn,n->hp', dtype)

    _, y = jax.lax.scan(one_position, jnp.zeros((heads, p, n), 'float32'),
                        (decay, dt, x, b, c))
    y = y + w['D'][None, :, None] * x
    gate = y.reshape(s, inner) * jax.nn.silu(z)
    return _mm(rms_norm(gate, w['norm_g'].astype('float32'),
                        cfg['rms_norm_eps']),
               w['out_w'].astype('float32'), 'si,ih->sh', dtype)


@functools.partial(jax.jit, static_argnames=('cfg_key', 'held', 'kind',
                                             'tie', 'dtype'))
def layer(x, w, cfg_key, held, kind, tie, dtype):
    """One block over a whole sequence x (S, hidden); ``w`` the layer's
    leaves by their short names. Returns (y, near): the block's output and
    the positions (S,) whose selection nearly ties within ``tie``."""
    cfg = dict(cfg_key)
    s, d, m = x.shape[0], cfg['head_dim'], cfg['residual_multiplier']
    r = rms_norm(x, w['ln1_g'].astype('float32'), cfg['rms_norm_eps'])
    if kind == MAMBA:
        mixed = mamba(r, w, cfg, dtype)
    else:
        q, k, v = (_mm(r, w[name].astype('float32'), 'sh,ho->so', dtype)
                   .reshape(s, -1, d) for name in ('q_w', 'k_w', 'v_w'))
        mixed = _mm(attention(q, k, v, cfg['attention_multiplier'], dtype),
                    w['o_w'].astype('float32'), 'so,oh->sh', dtype)
    u = x + m * mixed
    n = rms_norm(u, w['ln2_g'].astype('float32'), cfg['rms_norm_eps'])
    logit = _mm(n, w['router_w'].astype('float32'), 'sh,he->se', dtype)
    k = cfg['num_experts_per_tok']
    ranked = jnp.argsort(-logit, -1)
    last, nxt = ranked[:, k - 1], ranked[:, k]      # k-th, (k+1)-th largest
    at = jnp.arange(s)
    chosen = logit >= logit[at, last][:, None]      # the top-k largest
    here = jnp.zeros(logit.shape[1], bool).at[jnp.asarray(held)].set(True)
    near = (logit[at, last] - logit[at, nxt] < tie) & (here[last] | here[nxt])
    gate = jax.nn.softmax(jnp.where(chosen, logit, -jnp.inf), -1)

    def add_expert(acc, leaf):
        w1, w3, w2, ge = leaf
        return acc + ge[:, None] * gated(n, w1, w3, w2, dtype), None

    mine = gate[:, jnp.asarray(held)].T             # (held, S)
    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                             (w['w1'], w['w3'], w['w2'], mine))
    shared = gated(n, w['s1'], w['s3'], w['s2'], dtype)
    return u + m * (routed + shared), near


@functools.partial(jax.jit, static_argnames=('eps', 'scale', 'dtype'))
def head(x, g, embed, eps, scale, dtype):
    n = rms_norm(x, g.astype('float32'), eps)
    return _mm(n, embed.astype('float32'), 'nh,vh->nv', dtype) / scale


def hidden(cfg, w, tokens, dtype=None):
    """One causal pass over ``tokens`` (S,): the hidden states before the
    final norm, (S, hidden), and the positions (S,) that nearly tie in some
    layer."""
    x = cfg['embedding_multiplier'] \
        * w['embed'][jnp.asarray(tokens)].astype('float32')
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
                       cfg['rms_norm_eps'], float(cfg['logits_scaling']),
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
