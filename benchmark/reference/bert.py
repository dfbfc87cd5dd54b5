"""Plain BERT pre-training reference: forward, loss, gradients, AdamW.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from Devlin et al. 2018 and google-research/bert ``modeling.py``: post-LN
encoder, exact (erf) GELU, learned positions, tied masked-LM decoder, NSP
head on the tanh-pooled first token. It imports nothing of the program and
reads only the configuration file's sizes; the weights it is given are the
benchmark's own (:func:`make_weights`), never the program's.

``dtype`` lowers every matrix product's operands to that type (float32
accumulation): ``None`` is the reference, ``float8_e4m3fn`` is the control
one precision below the configuration's bfloat16 compute.
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
    """Name -> shape of every leaf, in a fixed order."""
    v, u = cfg['vocab_size'], cfg['hidden_size']
    h, p = cfg['intermediate_size'], cfg['max_position_embeddings']
    shapes = {'word': (v, u), 'type': (cfg['type_vocab_size'], u),
              'pos': (p, u), 'emb_ln_g': (u,), 'emb_ln_b': (u,)}
    for i in range(cfg['num_hidden_layers']):
        shapes.update({
            'l%d.qkv_w' % i: (3 * u, u), 'l%d.qkv_b' % i: (3 * u,),
            'l%d.out_w' % i: (u, u), 'l%d.out_b' % i: (u,),
            'l%d.ln1_g' % i: (u,), 'l%d.ln1_b' % i: (u,),
            'l%d.ffn1_w' % i: (h, u), 'l%d.ffn1_b' % i: (h,),
            'l%d.ffn2_w' % i: (u, h), 'l%d.ffn2_b' % i: (u,),
            'l%d.ln2_g' % i: (u,), 'l%d.ln2_b' % i: (u,)})
    shapes.update({'pool_w': (u, u), 'pool_b': (u,),
                   'dec_w': (u, u), 'dec_b': (u,),
                   'dec_ln_g': (u,), 'dec_ln_b': (u,),
                   'mlm_b': (v,), 'nsp_w': (2, u), 'nsp_b': (2,)})
    return shapes


def make_weights(cfg, seed):
    """Every leaf on the device in one jitted call from the seed: normal
    matrices at ``initializer_range``, unit gains, and small non-zero
    biases so that no leaf's gradient path is hidden behind a zero."""
    return seeded_leaves(leaf_shapes(cfg), cfg['initializer_range'], seed)


def make_batches(cfg, traffic, seed, count):
    """``count`` seeded batches whose rows all differ: ids, segment ids,
    valid lengths, masked positions inside the valid part, and labels."""
    rs = np.random.RandomState(seed % (2 ** 31))
    b, s, p = traffic['batch'], traffic['seq_len'], traffic['masked']
    lo = max(p, int(s * traffic.get('min_valid_share', 1.0)))
    out = []
    for _ in range(count):
        valid = rs.randint(lo, s + 1, (b,))
        out.append({
            'ids': rs.randint(0, cfg['vocab_size'], (b, s)).astype('int32'),
            'types': (rs.rand(b, s) > 0.5).astype('int32'),
            'valid': valid.astype('int32'),
            'positions': np.stack([rs.choice(n, p, replace=False)
                                   for n in valid]).astype('int32'),
            'mlm_labels': rs.randint(0, cfg['vocab_size'],
                                     (b, p)).astype('int32'),
            'nsp_labels': rs.randint(0, 2, (b,)).astype('int32')})
    return out


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def loss_fn(w, batch, cfg, dtype=None):
    eps, heads = cfg['layer_norm_eps'], cfg['num_attention_heads']
    ids, valid = batch['ids'], batch['valid']
    b, s = ids.shape
    u = cfg['hidden_size']
    d = u // heads
    x = w['word'][ids] + w['type'][batch['types']] + w['pos'][:s][None]
    x = _ln(x, w['emb_ln_g'], w['emb_ln_b'], eps)
    bias = jnp.where(jnp.arange(s)[None, :] < valid[:, None], 0.0,
                     -1e9)[:, None, None, :]
    for i in range(cfg['num_hidden_layers']):
        p = lambda n: w['l%d.%s' % (i, n)]                 # noqa: E731
        qkv = _mm(x, p('qkv_w'), 'bsu,ou->bso', dtype) + p('qkv_b')
        q, k, v = [t.reshape(b, s, heads, d) for t in jnp.split(qkv, 3, -1)]
        sc = _mm(q / math.sqrt(d), k, 'bqhd,bkhd->bhqk', dtype) + bias
        att = jax.nn.softmax(sc, -1)
        ctx = _mm(att, v, 'bhqk,bkhd->bqhd', dtype).reshape(b, s, u)
        x = _ln(x + _mm(ctx, p('out_w'), 'bsu,ou->bso', dtype) + p('out_b'),
                p('ln1_g'), p('ln1_b'), eps)
        h = jax.nn.gelu(_mm(x, p('ffn1_w'), 'bsu,hu->bsh', dtype)
                        + p('ffn1_b'), approximate=False)
        x = _ln(x + _mm(h, p('ffn2_w'), 'bsh,uh->bsu', dtype) + p('ffn2_b'),
                p('ln2_g'), p('ln2_b'), eps)
    pooled = jnp.tanh(_mm(x[:, 0], w['pool_w'], 'bu,ou->bo', dtype)
                      + w['pool_b'])
    nsp = _mm(pooled, w['nsp_w'], 'bu,ou->bo', dtype) + w['nsp_b']
    g = jnp.take_along_axis(x, batch['positions'][..., None], 1)
    g = jax.nn.gelu(_mm(g, w['dec_w'], 'bpu,ou->bpo', dtype) + w['dec_b'],
                    approximate=False)
    g = _ln(g, w['dec_ln_g'], w['dec_ln_b'], eps)
    mlm = _mm(g, w['word'], 'bpu,vu->bpv', dtype) + w['mlm_b']
    return (jnp.mean(_xent(mlm, batch['mlm_labels']))
            + jnp.mean(_xent(nsp, batch['nsp_labels'])))


@functools.partial(jax.jit, static_argnames=('cfg_key', 'dtype', 'blocks'))
def _loss_and_grad(w, batch, cfg_key, dtype, blocks):
    """Mean loss and gradient over the batch, ``blocks`` equal blocks of
    rows at a time so that the float32 activations fit beside nothing."""
    cfg = dict(cfg_key)
    rows = batch['ids'].shape[0] // blocks
    cut = jax.tree_util.tree_map(
        lambda a: a.reshape((blocks, rows) + a.shape[1:]), batch)

    def one(carry, blk):
        loss, grad = jax.value_and_grad(loss_fn)(w, blk, cfg, dtype)
        return jax.tree_util.tree_map(jnp.add, carry, (loss, grad)), None

    zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, w))
    (loss, grad), _ = jax.lax.scan(one, zero, cut)
    return loss / blocks, jax.tree_util.tree_map(lambda g: g / blocks, grad)


def _cfg_key(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


@jax.jit
def _adamw(w, g, m, v, t, lr, wd, b1, b2, eps):
    eta = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(
        lambda v_, g_: b2 * v_ + (1 - b2) * jnp.square(g_), v, g)
    w = jax.tree_util.tree_map(
        lambda w_, m_, v_: w_ - eta * (m_ / (jnp.sqrt(v_) + eps) + wd * w_),
        w, m, v)
    return w, m, v


def train(cfg, weights, batches, dtype=None, blocks=4, drop_half=False):
    """Follow ``len(batches)`` AdamW steps. Returns the losses, the first
    step's gradient and the weights after the last step. ``drop_half``
    plants the fault of a step that leaves half of each batch out."""
    opt = cfg['optimizer']
    dtype = None if dtype is None else jnp.dtype(dtype)
    w = dict(weights)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad = [], None
    for t, batch in enumerate(batches, 1):
        batch = {k: jnp.asarray(a) for k, a in batch.items()}
        if drop_half:
            batch = {k: a[:a.shape[0] // 2] for k, a in batch.items()}
        while batch['ids'].shape[0] % blocks:
            blocks -= 1
        loss, grad = _loss_and_grad(w, batch, _cfg_key(cfg), dtype, blocks)
        if first_grad is None:
            first_grad = grad
        w, m, v = _adamw(w, grad, m, v, float(t), opt['learning_rate'],
                         opt['wd'], opt['beta1'], opt['beta2'],
                         opt['epsilon'])
        losses.append(float(loss))
    return losses, first_grad, w
