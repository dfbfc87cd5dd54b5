"""Plain reference for the ``xing4_0`` family (Xing4.0-29B-A4B): one full
forward over a whole sequence, given this chip's share of each expert layer.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the configuration file's keys and the equations below: the attention is
the non-absorbed one for every position (latent rows up-projected to keys
and values by head), there is no cache, no page, no kernel, no batching over
requests, and no blocks but the query blocks that memory forces. ``X`` (n, C)
is a token's residual streams, ``n`` = ``hc_mult``::

    X_0    = E[token] repeated n times
    sublayer F (attention, then MLP or experts), its own phi, a, b:
      r      = RMSNorm(vec(X))                     over all n*C values
      Hpre   = sigmoid(a_pre * (r phi_pre) + b_pre)
      Hpost  = 2 * sigmoid(a_post * (r phi_post) + b_post)
      M      = exp(clip(a_res * mat(r phi_res) + b_res, clamp_min, clamp_max))
      Hres   = hc_sinkhorn_iters times: M <- M / (colsum(M) + hc_eps);
                                        M <- M / (rowsum(M) + hc_eps)
      X'     = Hres X + outer(Hpost, F(RMSNorm_in(Hpre X)))
    attention F, input h, position t:
      cq = RMSNorm(h Wqa);  [q_nope | q_pe]_i = cq Wqb
      [c | k_pe] = h Wkva;  c = RMSNorm(c);  [k_nope | v]_i = c Wkvb
      s_i(t,u) = g * (q_nope_i(t).k_nope_i(u) + rope_t(q_pe_i).rope_u(k_pe)),
      u <= t;  out = concat_i(softmax_u(s_i) v_i) Wo
      g = (0.1 * mscale_all_dim * ln(factor) + 1)^2 / sqrt(nope + rope)
      rope: YaRN's frequencies (theta, factor, original_max, beta_fast,
        beta_slow), pairs (j, j + rope / 2), cos and sin scaled by
        yarn(mscale) / yarn(mscale_all_dim)
    dense MLP (layers below first_k_dense_replace): D2(silu(D1 h) * D3 h)
    experts: s = sigmoid(h Wr) over all published experts;  T = the
      num_experts_per_tok largest of s + bias;
      g_e = routed_scaling_factor * s_e / sum_{f in T} s_f
      y = sum_{e in T and held here} g_e * expert_e(h) + shared(h)
    logits = RMSNorm_f(sum of the n streams) W_head
    next-token module: h' = [RMSNorm(x_t) ; RMSNorm(E[token_{t+1}])] Wm, one
      expert layer on h' repeated n times, RMSNorm of its own on the summed
      streams, the model's head; x_t the summed streams before RMSNorm_f

**Departures from the published description**, each also under ``assumed`` in
the configuration file: the streams start as n copies of the embedding and
end as their sum (the config names neither); a column pass then a row pass
make one Sinkhorn iteration, ``hc_eps`` added to each divisor; the read
stream passes an RMSNorm of its own before F; RoPE pairs are split in
halves, not interleaved; what the absent chips' experts would add is left
out, as in the program. ``dtype`` lowers every matrix product's operands
(the control); ``None`` is the reference. Weights stay bfloat16 on the
device, in the layout the program takes, and are raised to float32 one
layer, one expert at a time. The program rounds a latent row to bfloat16 as
it is cached; the reference does not.

**Near ties are left out by rule** (PR 30's, widened from ranks k and k+1 to
every expert held here): a position is *near tied* if in some expert layer a
shift of the biased scores, as computed here, by less than
``precision.router_tie_margin`` would change which of the experts held here
are among the k selected: a selected one that is held here lies that close
above the best one left out, or one held here and left out lies that close
below the weakest selected (three scores within the margin count: the 6th can
pass the 4th). The reference's own pass (``dtype`` None) answers a near-tied
position with a row of zeros: every token is then as good as the best. The
rule looks at the reference's scores alone, never at what was served.
"""
import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from .common import HIGHEST
from .common import cfg_key as _cfg_key
from .common import mm as _mm

# widths a sequence is padded to: few shapes, so few compiles (causal, so
# padding on the right changes nothing)
WIDTHS = (256, 1024, 2048, 4096, 8192, 12288, 17408)
QUERY_BLOCK = 256              # every width is a whole number of blocks
# leaves kept in float32: the hyper-connections' scales and biases and the
# router's selection bias
FLOAT32_LEAVES = ('hc1_a', 'hc1_b', 'hc2_a', 'hc2_b', 'router_b')


def sizes(cfg):
    """The sizes the equations need, from the configuration's keys."""
    return dict(
        hidden=cfg['hidden_size'], heads=cfg['num_attention_heads'],
        q_rank=cfg['q_lora_rank'], kv_rank=cfg['kv_lora_rank'],
        nope=cfg['qk_nope_head_dim'], rope=cfg['qk_rope_head_dim'],
        v=cfg['v_head_dim'], dense=cfg['intermediate_size'],
        ffn=cfg['moe_intermediate_size'],
        shared=cfg['n_shared_experts'] * cfg['moe_intermediate_size'],
        held=len(cfg['held_experts']),
        experts=cfg['published']['n_routed_experts'],
        top_k=cfg['num_experts_per_tok'], n=cfg['hc_mult'],
        layers=cfg['num_hidden_layers'],
        dense_layers=cfg['first_k_dense_replace'], vocab=cfg['vocab_size'])


def layer_shapes(cfg, dense):
    z = sizes(cfg)
    c, n, h = z['hidden'], z['n'], z['heads']
    shapes = {
        'ln1_g': (c,), 'ln2_g': (c,),
        'q_a': (c, z['q_rank']), 'q_a_g': (z['q_rank'],),
        'q_b': (z['q_rank'], h * (z['nope'] + z['rope'])),
        'kv_a': (c, z['kv_rank'] + z['rope']), 'kv_a_g': (z['kv_rank'],),
        'kv_b': (z['kv_rank'], h * (z['nope'] + z['v'])),
        'o_w': (h * z['v'], c)}
    for j in (1, 2):
        shapes.update({'hc%d_g' % j: (n * c,),
                       'hc%d_phi' % j: (n * c, 2 * n + n * n),
                       'hc%d_a' % j: (3,), 'hc%d_b' % j: (2 * n + n * n,)})
    if dense:
        shapes.update({'d1': (c, z['dense']), 'd3': (c, z['dense']),
                       'd2': (z['dense'], c)})
    else:
        shapes.update({
            'router_w': (c, z['experts']), 'router_b': (z['experts'],),
            'w1': (z['held'], c, z['ffn']), 'w3': (z['held'], c, z['ffn']),
            'w2': (z['held'], z['ffn'], c),
            's1': (c, z['shared']), 's3': (c, z['shared']),
            's2': (z['shared'], c)})
    return shapes


def leaf_shapes(cfg):
    z = sizes(cfg)
    c = z['hidden']
    shapes = {'embed': (z['vocab'], c), 'head': (z['vocab'], c),
              'lnf_g': (c,)}
    for i in range(z['layers']):
        shapes.update({'l%d.%s' % (i, k): v for k, v in
                       layer_shapes(cfg, i < z['dense_layers']).items()})
    if cfg['num_nextn_predict_layers']:
        shapes.update({'mtp.hnorm_g': (c,), 'mtp.enorm_g': (c,),
                       'mtp.lnf_g': (c,), 'mtp.proj': (2 * c, c)})
        shapes.update({'mtp.' + k: v
                       for k, v in layer_shapes(cfg, False).items()})
    return shapes


def leaf_std(cfg, name, shape):
    """Standard deviation of one normal leaf (the configuration's ``init``
    block says why): inputs of a product at 1/sqrt(fan-in), so that what
    they give has unit scale; branch outputs, the query, the value side of
    ``Wkvb`` and the router at gains of their own."""
    init = cfg['init']
    leaf = name.split('.')[-1]
    if leaf == 'embed':
        return init['embed_std']
    if leaf == 'head':
        return init['head_gain'] / math.sqrt(shape[-1])
    gain = {'o_w': init['attn_gain'], 'd2': init['dense_gain'],
            's2': init['shared_gain'], 'w2': init['routed_gain'],
            'q_b': init['query_gain'], 'kv_b': 1.0 / init['latent_gain'],
            'router_w': init['router_gain']}.get(leaf, 1.0)
    return gain / math.sqrt(shape[-2])


def make_weights(cfg, seed):
    """Every leaf on the device in one jitted call from the seed, in the
    bfloat16 they are served in and the stacked layout the program takes;
    the hyper-connections' ``a`` and ``b`` and the router's bias float32."""
    shapes = leaf_shapes(cfg)
    init, n = cfg['init'], cfg['hc_mult']

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            leaf = name.split('.')[-1]
            if leaf in ('hc1_a', 'hc2_a'):
                out[name] = jnp.asarray(
                    [init['hc_a_pre'], init['hc_a_post'], init['hc_a_res']],
                    'float32')
            elif leaf in ('hc1_b', 'hc2_b'):
                out[name] = jnp.concatenate([
                    jnp.zeros((2 * n,), 'float32'),
                    init['hc_b_res_diagonal'] * jnp.eye(n).reshape(-1)])
            elif leaf == 'router_b':
                out[name] = init['router_bias_std'] * jax.random.normal(
                    k, shape, 'float32')
            elif leaf == 'kv_a_g':
                out[name] = (init['latent_gain'] * (
                    1.0 + init['gain_std'] * jax.random.normal(
                        k, shape, 'float32'))).astype('bfloat16')
            elif leaf.endswith('_g'):
                out[name] = (1.0 + init['gain_std']
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


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(cfg):
    """The ``rope / 2`` frequencies: below ``low`` turns the unscaled one,
    above ``high`` the interpolated one (1 / factor), a linear ramp
    between."""
    rs, dim, theta = cfg['rope_scaling'], cfg['qk_rope_head_dim'], \
        cfg['rope_theta']

    def dims_at(turns):
        return dim * math.log(rs['original_max_position_embeddings']
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dims_at(rs['beta_fast'])), 0)
    high = min(math.ceil(dims_at(rs['beta_slow'])), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype='float64') / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (plain / rs['factor'] * ramp
            + plain * (1.0 - ramp)).astype('float32')


def rope(x, cfg):
    """x (S, ..., rope) rotated at positions 0 .. S - 1."""
    rs = cfg['rope_scaling']
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype='float32')[:, None] \
        * jnp.asarray(yarn_frequencies(cfg))[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    scale = yarn_mscale(rs['factor'], rs['mscale']) \
        / yarn_mscale(rs['factor'], rs['mscale_all_dim'])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(h, w, cfg, dtype):
    """Latent attention over a whole sequence h (S, hidden), not absorbed:
    every latent row is up-projected to its keys and values by head."""
    z = sizes(cfg)
    s, heads, nope = h.shape[0], z['heads'], z['nope']
    rs = cfg['rope_scaling']
    f32 = lambda name: w[name].astype('float32')             # noqa: E731
    cq = rms_norm(_mm(h, f32('q_a'), 'sh,hr->sr', dtype), f32('q_a_g'),
                  cfg['rms_norm_eps'])
    q = _mm(cq, f32('q_b'), 'sr,ro->so', dtype).reshape(
        s, heads, nope + z['rope'])
    kv = _mm(h, f32('kv_a'), 'sh,hr->sr', dtype)
    c = rms_norm(kv[:, :z['kv_rank']], f32('kv_a_g'), cfg['rms_norm_eps'])
    k_pe = rope(kv[:, z['kv_rank']:], cfg)
    kvb = _mm(c, f32('kv_b'), 'sc,co->so', dtype).reshape(
        s, heads, nope + z['v'])
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_pe[:, None], (s, heads, z['rope']))], -1)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], -1)
    v = kvb[..., nope:]
    g = yarn_mscale(rs['factor'], rs['mscale_all_dim']) ** 2 \
        / math.sqrt(nope + z['rope'])
    keys = jnp.arange(s)[None, :]
    block = min(QUERY_BLOCK, s)

    def one_block(at):
        qb = jax.lax.dynamic_slice_in_dim(q, at, block, 0)
        t = at + jnp.arange(block)[:, None]
        sc = _mm(qb * g, k, 'qhd,khd->hqk', dtype) \
            + jnp.where(keys <= t, 0.0, -1e9)[None]
        return _mm(jax.nn.softmax(sc, -1), v, 'hqk,khd->qhd', dtype)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    ctx = out.reshape(s, heads * z['v'])
    return _mm(ctx, f32('o_w'), 'so,oh->sh', dtype)


def gated(h, w1, w3, w2, dtype):
    """W2(silu(W1 h) * W3 h), the weights raised to float32."""
    w1, w3, w2 = (w.astype('float32') for w in (w1, w3, w2))
    u = jax.nn.silu(_mm(h, w1, 'sh,hf->sf', dtype)) \
        * _mm(h, w3, 'sh,hf->sf', dtype)
    return _mm(u, w2, 'sf,fh->sh', dtype)


def experts(h, w, cfg, held, dtype):
    """Routed experts held here plus the shared expert, and how tight each
    position's selection is: the smallest shift of biased scores that
    would change which of the experts held here are selected."""
    k = cfg['num_experts_per_tok']
    score = jax.nn.sigmoid(_mm(h, w['router_w'].astype('float32'),
                               'sh,he->se', dtype))
    biased = score + w['router_b']
    chosen = biased >= jax.lax.top_k(biased, k)[0][:, -1:]
    here = jnp.zeros(score.shape[1], bool).at[jnp.asarray(held)].set(True)

    def lowest(mask):
        return jnp.min(jnp.where(mask, biased, jnp.inf), -1)

    def highest(mask):
        return jnp.max(jnp.where(mask, biased, -jnp.inf), -1)

    # a selected expert held here falls behind the best one left out, or
    # one held here and left out passes the weakest selected
    tight = jnp.minimum(lowest(chosen & here) - highest(~chosen),
                        lowest(chosen) - highest(~chosen & here))
    picked = jnp.where(chosen, score, 0.0)
    gate = cfg['routed_scaling_factor'] * picked \
        / jnp.sum(picked, -1, keepdims=True)

    def add_expert(acc, leaf):
        w1, w3, w2, ge = leaf
        return acc + ge[:, None] * gated(h, w1, w3, w2, dtype), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (w['w1'], w['w3'], w['w2'], gate[:, jnp.asarray(held)].T))
    return routed + gated(h, w['s1'], w['s3'], w['s2'], dtype), tight


def coefficients(x, w, j, cfg, dtype):
    """Hpre (S, n), Hpost (S, n) and the doubly stochastic Hres (S, n, n)
    of sublayer ``j`` from the streams x (S, n, hidden)."""
    n, s = cfg['hc_mult'], x.shape[0]
    r = rms_norm(x.reshape(s, -1), w['hc%d_g' % j].astype('float32'),
                 cfg['rms_norm_eps'])
    z = _mm(r, w['hc%d_phi' % j].astype('float32'), 'sk,ko->so', dtype)
    a, b = w['hc%d_a' % j], w['hc%d_b' % j]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * z[:, 2 * n:] + b[2 * n:],
                         cfg['mhc_h_res_clamp_min'],
                         cfg['mhc_h_res_clamp_max'])).reshape(s, n, n)
    for _ in range(cfg['hc_sinkhorn_iters']):
        m = m / (m.sum(1, keepdims=True) + cfg['hc_eps'])    # columns
        m = m / (m.sum(2, keepdims=True) + cfg['hc_eps'])    # rows
    return pre, post, m


def sublayer(x, w, j, cfg, dtype, fn):
    pre, post, res = coefficients(x, w, j, cfg, dtype)
    read = jnp.einsum('sn,snc->sc', pre, x, precision=HIGHEST)
    out = fn(rms_norm(read, w['ln%d_g' % j].astype('float32'),
                      cfg['rms_norm_eps']))
    return jnp.einsum('snm,smc->snc', res, x, precision=HIGHEST) \
        + post[:, :, None] * out[:, None, :]


def _frozen(cfg):
    """What a layer needs of the configuration, as a hashable static
    argument: its plain sizes, the YaRN block, the experts held and the
    router's width."""
    return (_cfg_key(cfg), tuple(sorted(cfg['rope_scaling'].items())),
            tuple(cfg['held_experts']), cfg['published']['n_routed_experts'])


def _thawed(frozen):
    key, rs, held, experts = frozen
    return dict(key, rope_scaling=dict(rs), held_experts=list(held),
                published={'n_routed_experts': experts})


@functools.partial(jax.jit, static_argnames=('frozen', 'dense', 'dtype'))
def layer(x, w, frozen, dense, dtype):
    """One layer over a whole sequence's streams x (S, n, hidden); ``w`` the
    layer's leaves by their short names. Returns (x', tight): how tight
    each position's selection is (:func:`experts`; infinite in a dense
    layer)."""
    cfg = _thawed(frozen)
    held = frozen[2]
    x = sublayer(x, w, 1, cfg, dtype,
                 lambda h: attention(h, w, cfg, dtype))
    tight = []

    def second(h):
        if dense:
            tight.append(jnp.full(h.shape[0], jnp.inf))
            return gated(h, w['d1'], w['d3'], w['d2'], dtype)
        out, gap = experts(h, w, cfg, held, dtype)
        tight.append(gap)
        return out

    return sublayer(x, w, 2, cfg, dtype, second), tight[0]


@functools.partial(jax.jit, static_argnames=('eps', 'dtype'))
def head(summed, g, table, eps, dtype):
    n = rms_norm(summed, g.astype('float32'), eps)
    return _mm(n, table.astype('float32'), 'nh,vh->nv', dtype)


def _leaves(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def hidden(cfg, w, tokens, dtype=None):
    """One causal pass over ``tokens`` (S,): the summed streams before the
    final norm, (S, hidden), and each position's tightest selection over
    the expert layers, (S,) (:func:`experts`)."""
    e = w['embed'][jnp.asarray(tokens)].astype('float32')
    x = jnp.broadcast_to(e[:, None], (e.shape[0], cfg['hc_mult'],
                                      e.shape[1]))
    frozen = _frozen(cfg)
    tightest = jnp.full(x.shape[0], jnp.inf)
    for i in range(cfg['num_hidden_layers']):
        x, tight = layer(x, _leaves(w, 'l%d.' % i), frozen,
                         i < cfg['first_k_dense_replace'], dtype)
        tightest = jnp.minimum(tightest, tight)
    return x.sum(1), tightest


def mtp_logits(cfg, w, tokens, dtype=None):
    """The next-token module over ``tokens`` (S,): row t of S - 1 holds the
    logits of token t + 2."""
    summed, _ = hidden(cfg, w, tokens, dtype)
    m = _leaves(w, 'mtp.')
    eps = cfg['rms_norm_eps']
    nxt = w['embed'][jnp.asarray(tokens)[1:]].astype('float32')
    both = jnp.concatenate(
        [rms_norm(summed[:-1], m['hnorm_g'].astype('float32'), eps),
         rms_norm(nxt, m['enorm_g'].astype('float32'), eps)], -1)
    h = _mm(both, m['proj'].astype('float32'), 'sk,kh->sh', dtype)
    x = jnp.broadcast_to(h[:, None], (h.shape[0], cfg['hc_mult'],
                                      h.shape[1]))
    x, _tight = layer(x, m, _frozen(cfg), False, dtype)
    return head(x.sum(1), m['lnf_g'], w['head'], eps, dtype)


def next_token_logits(cfg, weights, prompts, outputs, dtype=None):
    """For each request, the logits that chose each served token: one
    teacher-forced pass over prompt + served tokens, a request at a time,
    padded on the right to one of ``WIDTHS``.
    Returns a list of (len(output), V) float32 arrays; without ``dtype``
    the rows of near-tied positions are zeros (the module's rule)."""
    dtype = None if dtype is None else jnp.dtype(dtype)
    margin = float(cfg['precision']['router_tie_margin'])
    out, left_out, rows_in_all = [], 0, 0
    with jax.default_matmul_precision('highest'):
        for p, o in zip(prompts, outputs):
            n = len(p) + len(o)
            width = next((w for w in WIDTHS if w >= n),
                         -(-n // QUERY_BLOCK) * QUERY_BLOCK)
            toks = np.zeros((width,), 'int32')
            toks[:len(p)] = p
            toks[len(p):n] = o
            x, tightest = hidden(cfg, weights, toks, dtype)
            rows = np.arange(len(p) - 1, n - 1)
            pad = -len(rows) % 128           # few distinct shapes
            at = jnp.asarray(np.concatenate([rows, np.zeros(pad, 'int64')]),
                             'int32')
            got = head(x[at], weights['lnf_g'], weights['head'],
                       cfg['rms_norm_eps'], dtype)
            got = np.asarray(got)[:len(rows)]
            if dtype is None:
                tied = np.asarray(tightest)[rows] < margin
                got = np.where(tied[:, None], np.float32(0), got)
                left_out += int(tied.sum())
                rows_in_all += len(rows)
            out.append(got)
    if dtype is None:
        print('[reference] %d of %d positions near tied and left out'
              % (left_out, rows_in_all), file=sys.stderr, flush=True)
    return out
