"""Operations and bytes that serving the ``xing4_0`` family needs on this
chip's share, from the configuration's shapes alone: two per multiply-add,
and never more than the least an implementation must do. A token goes
through its layer's low-rank query and latent projections, the absorbed
products (its query through the key half of ``Wkvb``, its context through
the value half: as many multiply-adds as ``Wkvb`` has weights), the output
projection, two hyper-connections (a product of the ``n C`` stream values
with ``2 n + n n`` columns, and the read, write and mixing of ``n`` streams),
and a dense MLP or the router, the shared expert and, on average, ``top_k *
held / experts`` routed experts. A decode step reads every weight held here
once, and of every cached token it can see one latent row a layer at the
configuration's stored width, ``kv_lora_rank + qk_rope_head_dim`` columns:
what a layout pads a row to is the program's cost, not the yardstick's."""
import numpy as np

BYTES = {'float32': 4, 'bfloat16': 2}


def _sizes(cfg):
    heads = cfg['num_attention_heads']
    return dict(
        c=cfg['hidden_size'], heads=heads, q_rank=cfg['q_lora_rank'],
        kv_rank=cfg['kv_lora_rank'], rope=cfg['qk_rope_head_dim'],
        q=heads * (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']),
        kvb=heads * (cfg['qk_nope_head_dim'] + cfg['v_head_dim']),
        o=heads * cfg['v_head_dim'], dense=cfg['intermediate_size'],
        f=cfg['moe_intermediate_size'], shared=cfg['n_shared_experts'],
        held=len(cfg['held_experts']),
        experts=cfg['published']['n_routed_experts'],
        top_k=cfg['num_experts_per_tok'], n=cfg['hc_mult'],
        layers=cfg['num_hidden_layers'],
        dense_layers=cfg['first_k_dense_replace'], vocab=cfg['vocab_size'])


def row_columns(cfg):
    """Columns of one cached token in one layer: the latent and the roped
    key, 576 as published."""
    return cfg['kv_lora_rank'] + cfg['qk_rope_head_dim']


def attention_params(cfg):
    """The five projections of one layer's attention (28.4 M as
    published), without their two norms."""
    z = _sizes(cfg)
    return z['c'] * z['q_rank'] + z['q_rank'] * z['q'] \
        + z['c'] * (z['kv_rank'] + z['rope']) + z['kv_rank'] * z['kvb'] \
        + z['o'] * z['c']


def hyper_connection_params(cfg):
    """One sublayer's: phi, the norm over all streams, a and b."""
    z = _sizes(cfg)
    cols = 2 * z['n'] + z['n'] * z['n']
    return z['n'] * z['c'] * (cols + 1) + 3 + cols


def hyper_connection_flops(cfg):
    """One sublayer's for one token: the coefficients' product, and a
    multiply-add an element for the read (n C), the write (n C) and the
    mixing (n n C)."""
    z = _sizes(cfg)
    cols = 2 * z['n'] + z['n'] * z['n']
    return 2 * z['n'] * z['c'] * cols \
        + 2 * z['c'] * (2 * z['n'] + z['n'] * z['n'])


def expert_params(cfg):
    z = _sizes(cfg)
    return 3 * z['c'] * z['f']


def layer_params(cfg, dense):
    """Weights one layer holds here (a step reads each once): attention
    with its two low-rank norms, two hyper-connections, the two input
    norms, and a dense MLP or router (with its bias), shared and held
    experts."""
    z = _sizes(cfg)
    common = attention_params(cfg) + z['q_rank'] + z['kv_rank'] \
        + 2 * hyper_connection_params(cfg) + 2 * z['c']
    if dense:
        return common + 3 * z['c'] * z['dense']
    return common + z['c'] * z['experts'] + z['experts'] \
        + (z['shared'] + z['held']) * expert_params(cfg)


def layer_flops(cfg, dense):
    """Operations one token costs one layer, apart from the cached
    positions it attends."""
    z = _sizes(cfg)
    common = 2 * attention_params(cfg) + 2 * hyper_connection_flops(cfg)
    if dense:
        return common + 2 * 3 * z['c'] * z['dense']
    routed = z['top_k'] * z['held'] / z['experts']
    return common + 2 * (z['c'] * z['experts']
                         + (z['shared'] + routed) * expert_params(cfg))


def token_flops(cfg):
    """Operations one token costs all layers, apart from the positions it
    attends and the head."""
    z = _sizes(cfg)
    return z['dense_layers'] * layer_flops(cfg, True) \
        + (z['layers'] - z['dense_layers']) * layer_flops(cfg, False)


def weight_bytes(cfg):
    """What a step reads of the weights: every layer and the head (the
    embedding's rows aside: one a sequence)."""
    z = _sizes(cfg)
    return (z['dense_layers'] * layer_params(cfg, True)
            + (z['layers'] - z['dense_layers']) * layer_params(cfg, False)
            + z['c'] * z['vocab'] + z['c']) \
        * BYTES[cfg['precision']['weights']]


def mla_decode_attention(cfg, active, live_kv_tokens):
    """(operations, bytes) of ONE layer's absorbed attention over the
    cache in one step: ``active`` query rows of ``heads`` heads, each
    scoring the ``kv_lora_rank + qk_rope_head_dim`` columns of the cached
    rows its sequence can see and summing their ``kv_lora_rank`` latent
    columns; every such row read once, the queries read and the contexts
    written in float32."""
    z = _sizes(cfg)
    cols = row_columns(cfg)
    flops = 2 * z['heads'] * (cols + z['kv_rank']) * live_kv_tokens
    byts = cols * BYTES[cfg['precision']['kv_cache']] * live_kv_tokens \
        + 4 * active * z['heads'] * (cols + z['kv_rank'])
    return flops, byts


def decode_step(cfg, active, live_kv_tokens):
    """(operations, bytes) of one step that advances ``active`` sequences
    holding ``live_kv_tokens`` cached tokens between them."""
    z = _sizes(cfg)
    attn_ops, _ = mla_decode_attention(cfg, active, live_kv_tokens)
    flops = active * (token_flops(cfg) + 2 * z['c'] * z['vocab']) \
        + z['layers'] * attn_ops
    cache = z['layers'] * row_columns(cfg) * live_kv_tokens \
        * BYTES[cfg['precision']['kv_cache']]
    return flops, weight_bytes(cfg) + cache


def serve_flops_per_token(cfg, traffic):
    """Operations per output token where every document is resident: a
    request is a prefix hit whose last prompt token goes through the step
    and gives the first reply token, so each reply token is one sequence's
    share of one step: layers and head, and the cached positions before
    it. No document's prefill is counted: the window runs about none."""
    from ..loadgen import lognormal_grid
    z = _sizes(cfg)
    n = int(traffic['population'])
    prompt = np.asarray(lognormal_grid(traffic['prompt_len'], n), 'float64')
    output = np.asarray(lognormal_grid(traffic['output_len'], n), 'float64')
    p, o = prompt[:, None], output[None, :]
    # reply token j (0 based) attends p + j positions
    attended = (o * p + o * (o - 1) / 2).mean()
    per_position, _ = mla_decode_attention(cfg, 0, 1)
    work = output.mean() * (token_flops(cfg) + 2 * z['c'] * z['vocab']) \
        + z['layers'] * per_position * attended
    return work / output.mean()
