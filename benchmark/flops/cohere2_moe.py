"""Operations and bytes that serving the ``cohere2_moe`` family needs on
this chip's share, from the configuration's shapes alone: two per
multiply-add, and never more than the least an implementation must do. A
token goes through the attention projections of the heads held here, the
router, the four shared experts and, on average, ``top_k * held / experts``
routed experts; it attends to what its layer can see (everything on a full
layer, at most ``sliding_window`` positions on a sliding one). A decode step
reads every weight held here once, and the K and V its sequences can see,
at the configuration's stored width."""
import numpy as np

BYTES = {'float32': 4, 'bfloat16': 2}


def _sizes(cfg):
    h, d, f = cfg['hidden_size'], cfg['head_dim'], cfg['intermediate_size']
    kinds = cfg['layer_types']
    return dict(
        h=h, f=f, layers=len(kinds),
        sliding=sum(k == 'sliding_attention' for k in kinds),
        q=cfg['num_attention_heads'] * d,
        kv=cfg['num_key_value_heads'] * d,
        held=len(cfg['held_experts']),
        experts=cfg['published']['num_experts'],
        top_k=cfg['num_experts_per_tok'],
        shared=cfg['num_shared_experts'], vocab=cfg['vocab_size'],
        window=cfg['sliding_window'])


def layer_matmul_params(cfg):
    """Weights one token is multiplied through in one layer, on average:
    attention projections, router, shared experts, and the routed experts
    that are both selected and held here."""
    z = _sizes(cfg)
    attention = 2 * z['h'] * z['q'] + 2 * z['h'] * z['kv']
    expert = 3 * z['h'] * z['f']
    routed = z['top_k'] * z['held'] / z['experts']
    return attention + z['h'] * z['experts'] \
        + (z['shared'] + routed) * expert


def layer_params(cfg):
    """Weights one layer holds here (a step reads each once)."""
    z = _sizes(cfg)
    return 2 * z['h'] * z['q'] + 2 * z['h'] * z['kv'] \
        + z['h'] * z['experts'] + z['h'] \
        + (z['shared'] + z['held']) * 3 * z['h'] * z['f']


def seen(cfg, context):
    """Cached positions one query attends to, summed over the layers:
    ``context`` on a full layer, at most the window on a sliding one
    (``context`` a number or an array)."""
    z = _sizes(cfg)
    return (z['layers'] - z['sliding']) * context \
        + z['sliding'] * np.minimum(context, z['window'])


def decode_step(cfg, active, live_kv_tokens):
    """(operations, bytes) of one step that advances ``active`` sequences
    holding ``live_kv_tokens`` cached tokens between them (each at the mean
    length, which a window caps)."""
    z = _sizes(cfg)
    positions = active * seen(cfg, live_kv_tokens / max(active, 1e-9))
    flops = 2 * active * (z['layers'] * layer_matmul_params(cfg)
                          + z['h'] * z['vocab']) \
        + 4 * z['q'] * positions
    weights = (z['layers'] * layer_params(cfg) + z['h'] * z['vocab']
               + z['h']) * BYTES[cfg['precision']['weights']]
    cache = 2 * z['kv'] * positions * BYTES[cfg['precision']['kv_cache']]
    return flops, weights + cache


def serve_flops_per_token(cfg, traffic):
    """Operations per output token over the traffic's population: every
    prompt position through the layers once (the head for its last
    position only), every output token through layers and head, each
    attending to what its layer can see."""
    from ..loadgen import lognormal_grid
    z = _sizes(cfg)
    n = int(traffic['population'])
    prompt = np.asarray(lognormal_grid(traffic['prompt_len'], n), 'float64')
    output = np.asarray(lognormal_grid(traffic['output_len'], n), 'float64')
    # positions seen by queries 1..m, summed: a prefix sum over lengths
    upto = np.concatenate([[0.0], np.cumsum(seen(
        cfg, np.arange(1, int(prompt.max() + output.max()) + 1)))])
    p, o = prompt[:, None].astype(int), output[None, :].astype(int)
    attended = upto[p + o - 1].mean()    # prompt and all but the last reply
    per_token = 2 * z['layers'] * layer_matmul_params(cfg)
    head = 2 * z['h'] * z['vocab']
    work = (prompt.mean() + output.mean() - 1) * per_token \
        + output.mean() * head + 4 * z['q'] * attended
    return work / output.mean()
