"""Operations and bytes that serving the ``granitemoehybrid`` family needs on
this chip's share, from the configuration's shapes alone: two per
multiply-add, and never more than the least an implementation must do. A
token goes through its layer's mixer (a Mamba layer: the fused input
projection, the depthwise convolution, three multiply-adds an element of the
state, for decay, update and read-out, and the output projection; an
attention layer: the four projections and the positions it can see), the
router, the shared MLP and, on average, ``top_k * held / experts`` routed
experts. A decode step reads every weight held here once, reads and writes
the recurrent state of every sequence it advances, and reads the K and V
its sequences can see, at the configuration's stored widths."""
import numpy as np

BYTES = {'float32': 4, 'bfloat16': 2}


def _sizes(cfg):
    kinds = cfg['layer_types']
    heads, p = cfg['mamba_n_heads'], cfg['mamba_d_head']
    inner, n = heads * p, cfg['mamba_d_state']
    return dict(
        h=cfg['hidden_size'], f=cfg['intermediate_size'],
        fs=cfg['shared_intermediate_size'], layers=len(kinds),
        mamba=sum(k == 'mamba' for k in kinds),
        q=cfg['num_attention_heads'] * cfg['head_dim'],
        kv=cfg['num_key_value_heads'] * cfg['head_dim'],
        held=len(cfg['held_experts']),
        experts=cfg['published']['num_local_experts'],
        top_k=cfg['num_experts_per_tok'], vocab=cfg['vocab_size'],
        m_heads=heads, inner=inner, conv_width=inner + 2 * n,
        conv=cfg['mamba_d_conv'], state=heads * p * n)


def mamba_mixer_params(cfg):
    """Weights of one Mamba mixer: fused input projection, convolution
    (taps and bias), the recurrence's three vectors, the gated norm, the
    output projection."""
    z = _sizes(cfg)
    return z['h'] * (z['inner'] + z['conv_width'] + z['m_heads']) \
        + z['conv_width'] * (z['conv'] + 1) + 3 * z['m_heads'] \
        + z['inner'] + z['inner'] * z['h']


def mamba_mixer_flops(cfg):
    """Operations one token costs one Mamba mixer."""
    z = _sizes(cfg)
    return 2 * z['h'] * (z['inner'] + z['conv_width'] + z['m_heads']) \
        + 2 * z['conv_width'] * z['conv'] + 6 * z['state'] \
        + 2 * z['inner'] * z['h']


def attention_params(cfg):
    z = _sizes(cfg)
    return 2 * z['h'] * z['q'] + 2 * z['h'] * z['kv']


def mlp_params(cfg):
    """Router, shared MLP, held experts and the two norms of one layer."""
    z = _sizes(cfg)
    return z['h'] * z['experts'] + 3 * z['h'] * z['fs'] \
        + z['held'] * 3 * z['h'] * z['f'] + 2 * z['h']


def mlp_flops(cfg):
    """Operations one token costs the second half of a layer: router,
    shared MLP, and the routed experts both selected and held here."""
    z = _sizes(cfg)
    routed = z['top_k'] * z['held'] / z['experts']
    return 2 * (z['h'] * z['experts'] + 3 * z['h'] * z['fs']
                + routed * 3 * z['h'] * z['f'])


def state_bytes(cfg):
    """Recurrent state one sequence holds: every Mamba layer's carried
    state and its convolution's last inputs."""
    z = _sizes(cfg)
    prec = cfg['precision']
    return z['mamba'] * (
        z['state'] * BYTES[prec['ssm_state']]
        + (z['conv'] - 1) * z['conv_width'] * BYTES[prec['conv_state']])


def token_flops(cfg):
    """Operations one token costs all layers, apart from the positions an
    attention layer sees and the head."""
    z = _sizes(cfg)
    return z['mamba'] * mamba_mixer_flops(cfg) \
        + (z['layers'] - z['mamba']) * 2 * attention_params(cfg) \
        + z['layers'] * mlp_flops(cfg)


def decode_step(cfg, active, live_kv_tokens):
    """(operations, bytes) of one step that advances ``active`` sequences
    holding ``live_kv_tokens`` cached tokens between them."""
    z = _sizes(cfg)
    attn = z['layers'] - z['mamba']
    positions = attn * live_kv_tokens
    flops = active * (token_flops(cfg) + 2 * z['h'] * z['vocab']) \
        + 4 * z['q'] * positions
    weights = (z['mamba'] * mamba_mixer_params(cfg)
               + attn * attention_params(cfg)
               + z['layers'] * mlp_params(cfg)
               + z['h'] * z['vocab'] + z['h']) \
        * BYTES[cfg['precision']['weights']]
    state = 2 * active * state_bytes(cfg)
    cache = 2 * z['kv'] * positions * BYTES[cfg['precision']['kv_cache']]
    return flops, weights + state + cache


def serve_flops_per_token(cfg, traffic):
    """Operations per output token over the traffic's population: every
    prompt position through the layers once (the head for its last
    position only), every output token through layers and head, each
    attending on the attention layers to everything before it."""
    from ..loadgen import lognormal_grid
    z = _sizes(cfg)
    n = int(traffic['population'])
    prompt = np.asarray(lognormal_grid(traffic['prompt_len'], n), 'float64')
    output = np.asarray(lognormal_grid(traffic['output_len'], n), 'float64')
    # positions seen by queries 1..m, summed: m (m + 1) / 2 an attention layer
    m = prompt[:, None] + output[None, :] - 1
    attended = (z['layers'] - z['mamba']) * (m * (m + 1) / 2).mean()
    work = (prompt.mean() + output.mean() - 1) * token_flops(cfg) \
        + output.mean() * 2 * z['h'] * z['vocab'] + 4 * z['q'] * attended
    return work / output.mean()
