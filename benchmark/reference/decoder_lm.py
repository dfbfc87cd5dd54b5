"""Plain causal decoder reference (GPT-1's block: post-LN, learned
positions, tied head), one full forward over a whole sequence.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, from
Radford et al. 2018 and the configuration file's sizes; no cache, no
batching, nothing of the program. ``dtype`` lowers every matrix product's
operands (the control); ``None`` is the reference.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import cfg_key as _cfg_key
from .common import layer_norm as _ln
from .common import mm as _mm
from .common import seeded_leaves


def leaf_shapes(cfg):
    v, u, h = cfg['vocab_size'], cfg['n_embd'], cfg['intermediate_size']
    shapes = {'embed': (v, u), 'pos': (cfg['n_positions'], u)}
    for i in range(cfg['n_layer']):
        shapes.update({
            'l%d.qkv_w' % i: (3 * u, u), 'l%d.qkv_b' % i: (3 * u,),
            'l%d.out_w' % i: (u, u), 'l%d.out_b' % i: (u,),
            'l%d.ln1_g' % i: (u,), 'l%d.ln1_b' % i: (u,),
            'l%d.ffn1_w' % i: (h, u), 'l%d.ffn1_b' % i: (h,),
            'l%d.ffn2_w' % i: (u, h), 'l%d.ffn2_b' % i: (u,),
            'l%d.ln2_g' % i: (u,), 'l%d.ln2_b' % i: (u,)})
    return shapes


def make_weights(cfg, seed):
    """Every leaf on the device in one jitted call from the seed, in the
    float32 they are served in."""
    return seeded_leaves(leaf_shapes(cfg), cfg['initializer_range'], seed)


def hidden(w, tokens, cfg, dtype=None):
    """Final hidden states (B, S, U) of a causal pass over ``tokens``."""
    eps, heads, u = cfg['layer_norm_epsilon'], cfg['n_head'], cfg['n_embd']
    b, s = tokens.shape
    d = u // heads
    x = w['embed'][tokens] + w['pos'][:s][None]
    causal = jnp.where(jnp.arange(s)[None, :] <= jnp.arange(s)[:, None],
                       0.0, -1e9)[None, None]
    for i in range(cfg['n_layer']):
        p = lambda n: w['l%d.%s' % (i, n)]                 # noqa: E731
        qkv = _mm(x, p('qkv_w'), 'bsu,ou->bso', dtype) + p('qkv_b')
        q, k, v = [t.reshape(b, s, heads, d) for t in jnp.split(qkv, 3, -1)]
        sc = _mm(q / math.sqrt(d), k, 'bqhd,bkhd->bhqk', dtype) + causal
        att = jax.nn.softmax(sc, -1)
        ctx = _mm(att, v, 'bhqk,bkhd->bqhd', dtype).reshape(b, s, u)
        x = _ln(x + _mm(ctx, p('out_w'), 'bsu,ou->bso', dtype) + p('out_b'),
                p('ln1_g'), p('ln1_b'), eps)
        h = jax.nn.gelu(_mm(x, p('ffn1_w'), 'bsu,hu->bsh', dtype)
                        + p('ffn1_b'), approximate=False)
        x = _ln(x + _mm(h, p('ffn2_w'), 'bsh,uh->bsu', dtype) + p('ffn2_b'),
                p('ln2_g'), p('ln2_b'), eps)
    return x


@functools.partial(jax.jit, static_argnames=('cfg_key', 'dtype'))
def _logits_at(w, tokens, rows, cols, cfg_key, dtype):
    x = hidden(w, tokens, dict(cfg_key), dtype)[rows, cols]
    return _mm(x, w['embed'], 'nu,vu->nv', dtype)


def _cfg_key(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def next_token_logits(cfg, weights, prompts, outputs, dtype=None, rows=8):
    """For each request, the logits that chose each served token: one
    teacher-forced pass over prompt + served tokens, ``rows`` requests at a
    time, padded on the right (causal, so padding changes nothing).
    Returns a list of (len(output), V) float32 arrays."""
    dtype = None if dtype is None else jnp.dtype(dtype)
    width = -(-max(len(p) + len(o) for p, o in zip(prompts, outputs))
              // 64) * 64
    out = []
    for at in range(0, len(prompts), rows):
        ps, os_ = prompts[at:at + rows], outputs[at:at + rows]
        toks = np.zeros((rows, width), 'int32')
        r, c = [], []
        for i, (p, o) in enumerate(zip(ps, os_)):
            toks[i, :len(p)] = p
            toks[i, len(p):len(p) + len(o)] = o
            r += [i] * len(o)
            c += range(len(p) - 1, len(p) - 1 + len(o))
        pad = -len(r) % 256       # few distinct shapes, so few compiles
        got = np.asarray(_logits_at(
            weights, jnp.asarray(toks), jnp.asarray(r + [0] * pad, 'int32'),
            jnp.asarray(c + [0] * pad, 'int32'), _cfg_key(cfg), dtype))
        k = 0
        for o in os_:
            out.append(got[k:k + len(o)])
            k += len(o)
    return out
