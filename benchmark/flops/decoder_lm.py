"""Operations and bytes a causal decoder's serving needs, from the
configuration's shapes alone: two per multiply-add; a decode step reads the
weights once and the K and V of the live tokens, at the configuration's
stored width."""
import statistics

BYTES = {'float32': 4, 'bfloat16': 2}


def matmul_params(cfg):
    """Weights that every token is multiplied through (tied head once)."""
    u, h = cfg['n_embd'], cfg['intermediate_size']
    return cfg['n_layer'] * (4 * u * u + 2 * u * h) + u * cfg['vocab_size']


def all_params(cfg):
    u = cfg['n_embd']
    return matmul_params(cfg) + cfg['n_positions'] * u \
        + cfg['n_layer'] * (9 * u + cfg['intermediate_size'])


def token_flops(cfg, context):
    """One token through the stack against ``context`` cached tokens."""
    return 2 * matmul_params(cfg) \
        + 4 * cfg['n_embd'] * context * cfg['n_layer']


def decode_step(cfg, active, live_kv_tokens):
    """(operations, bytes) of one step that advances ``active`` sequences
    holding ``live_kv_tokens`` cached tokens between them."""
    u, layers = cfg['n_embd'], cfg['n_layer']
    flops = 2 * matmul_params(cfg) * active + 4 * u * layers * live_kv_tokens
    wb = BYTES[cfg['precision']['weights']]
    kb = BYTES[cfg['precision']['kv_cache']]
    return flops, all_params(cfg) * wb + 2 * u * layers * kb * live_kv_tokens


def serve_flops_per_token(cfg, traffic):
    """Operations per output token at the traffic's mean lengths: its own
    decode pass, and its share of the prompt's prefill."""
    from ..loadgen import lognormal_grid
    n = int(traffic['population'])
    prompt = statistics.mean(lognormal_grid(traffic['prompt_len'], n))
    output = statistics.mean(lognormal_grid(traffic['output_len'], n))
    decode = token_flops(cfg, prompt + output / 2)
    prefill = prompt * token_flops(cfg, prompt / 2)
    return decode + prefill / output
