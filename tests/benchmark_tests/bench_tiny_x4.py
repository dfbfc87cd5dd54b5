"""The ``xing4_0`` family's cell at a toy size, laid over the toy tree of
``bench_tiny``: hidden 48, three layers (one dense, two of 8 experts of
which 4 are held: 0, 1, 2, 5; top-2 and a shared expert), 4 heads of 16 + 8
on a latent of 32 with a roped key of 8 (a cache row of 40 columns padded to
128), low-rank queries of 24, four residual streams, YaRN from 32 positions
by a factor of 4, page 4, vocabulary 96, float32 so that a flipped routing
selection is the only thing that can separate program and reference."""
import json
import os

import bench_tiny

CELL = 'x4-tiny-docqa'
LIMIT = 2e-4


def config(**over):
    with open(os.path.join(bench_tiny.REPO, 'benchmark', 'configs',
                           'xing4.0-29b-a4b-10l-1of4.json')) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=48, intermediate_size=96, kv_lora_rank=32,
               q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, num_attention_heads=4, num_key_value_heads=4,
               moe_intermediate_size=32, n_routed_experts=4,
               num_experts_per_tok=2, num_hidden_layers=3,
               first_k_dense_replace=1, vocab_size=96,
               max_position_embeddings=96, held_experts=[0, 1, 2, 5])
    cfg['rope_scaling'] = dict(cfg['rope_scaling'], factor=4,
                               original_max_position_embeddings=32)
    cfg['published'] = dict(cfg['published'], n_routed_experts=8)
    cfg['precision'] = dict(cfg['precision'], weights='float32',
                            kv_cache='float32')
    # the toy's own gains: with 96 tokens to choose from and tens of keys
    # a fault has to move a logit far to change a choice, so attention is
    # peaked (scores of deviation 3) and the coefficients' scales are 1
    cfg['init'] = dict(cfg['init'], query_gain=1.5, hc_a_pre=1.0,
                       hc_a_post=1.0)
    cfg['deployment'] = dict(
        slots=4, page_size=4, pages=129, prefill_buckets=[8, 16, 32, 64],
        max_queue=16, max_new_tokens=24, timeout_s=60.0, emit_logits=False,
        prefill_interleave=2, model=dict(prefill_block=8))
    cfg.update(over)
    return cfg


def build(tree):
    """``bench_tiny``'s tree with this family's toy cell added."""
    tree = bench_tiny.build(tree)
    bench_tiny._dump(config(), tree, 'benchmark', 'configs', 'x4-tiny.json')
    bench_tiny._dump(
        dict(loop='closed', clients=6, stream=True, population=8,
             ramp_seconds=1.5, grace_seconds=30.0, checked_requests=40,
             trace_seconds=1.0,
             prompt_len={'median': 30, 'sigma': 0.5, 'min': 9, 'max': 60},
             output_len={'median': 10, 'sigma': 0.4, 'min': 4, 'max': 24}),
        tree, 'benchmark', 'traffic', 'x4-docqa-tiny.json')
    bench_tiny._dump({'logit_gap_max': LIMIT}, tree, 'benchmark', 'limits',
                     CELL + '.json')
    path = os.path.join(tree, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append(
        {'name': 'x4-tiny', 'source': 'toy', 'reduced': [], 'why': 'toy',
         'file': 'benchmark/configs/x4-tiny.json'})
    bench['workloads'].append(
        {'name': CELL, 'config': 'x4-tiny', 'traffic': 'x4-docqa-tiny',
         'chips': 1, 'why': 'toy'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if m['name'] == 'serve_tokens_per_s' or m['name'].endswith('.x4'):
            m['workloads'].append(CELL)
    bench_tiny._dump(bench, tree, 'BENCHMARK.json')
    return tree
