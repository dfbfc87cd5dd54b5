"""Operations a BERT pre-training step needs, from the configuration's
shapes alone: two per multiply-add, backward twice the forward, nothing
recomputed, and no credit for how a program gathers rows."""


def forward_flops_per_sample(cfg, traffic):
    u, h = cfg['hidden_size'], cfg['intermediate_size']
    s, p, v = traffic['seq_len'], traffic['masked'], cfg['vocab_size']
    per_token = (2 * u * 3 * u       # q, k, v projections
                 + 2 * s * u         # scores against s keys, all heads
                 + 2 * s * u         # probabilities times values
                 + 2 * u * u         # output projection
                 + 2 * 2 * u * h)    # the two feed-forward products
    encoder = cfg['num_hidden_layers'] * s * per_token
    heads = (2 * u * u + 2 * 2 * u          # pooler, next-sentence
             + p * (2 * u * u + 2 * u * v))  # transform, tied decoder
    return encoder + heads


def train_flops_per_sample(cfg, traffic):
    return 3 * forward_flops_per_sample(cfg, traffic)
