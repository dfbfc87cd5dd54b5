"""The ``granitemoehybrid`` family's cell at a toy size, laid over the toy
tree of ``bench_tiny``: hidden 64, layers mamba x2 + attention + mamba, 8
Mamba heads of 16 with a state of 16 and chunks of 8, 4 query heads on 2 KV
heads of 16, 8 experts of which 4 are held (0, 1, 2, 5), top-3, a shared MLP
of 48, page 4, vocabulary 96, float32 so that a flipped routing selection
is the only thing that can separate program and reference."""
import json
import os

import bench_tiny

CELL = 'g4h-tiny-sat'
LIMIT = 2e-5


def config():
    with open(os.path.join(bench_tiny.REPO, 'benchmark', 'configs',
                           'granite-4.0-h-small-10l-1of2.json')) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, head_dim=16, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=32,
               shared_intermediate_size=48, num_local_experts=4,
               num_experts_per_tok=3, mamba_n_heads=8, mamba_d_head=16,
               mamba_d_state=16, mamba_chunk_size=8, num_hidden_layers=4,
               layer_types=['mamba', 'mamba', 'attention', 'mamba'],
               attention_multiplier=0.0625, vocab_size=96,
               max_position_embeddings=64, held_experts=[0, 1, 2, 5])
    cfg['published'] = dict(cfg['published'], num_local_experts=8)
    cfg['init'] = dict(cfg['init'], embed_std=0.005, query_gain=8.0)
    cfg['precision'] = dict(cfg['precision'], weights='float32',
                            kv_cache='float32', conv_state='float32')
    cfg['deployment'] = dict(
        slots=4, page_size=4, pages=65, prefill_buckets=[8, 16, 32],
        max_queue=16, max_new_tokens=24, timeout_s=60.0, emit_logits=False,
        prefill_interleave=2, model=dict(prefill_block=8))
    return cfg


def build(tree):
    """``bench_tiny``'s tree with this family's toy cell added."""
    tree = bench_tiny.build(tree)
    bench_tiny._dump(config(), tree, 'benchmark', 'configs',
                     'g4h-tiny.json')
    bench_tiny._dump(
        dict(loop='closed', clients=6, stream=True, population=32,
             ramp_seconds=0.3, grace_seconds=30.0, checked_requests=40,
             trace_seconds=1.0,
             prompt_len={'median': 12, 'sigma': 0.6, 'min': 4, 'max': 30},
             output_len={'median': 12, 'sigma': 0.4, 'min': 4, 'max': 24}),
        tree, 'benchmark', 'traffic', 'g4h-sat-tiny.json')
    bench_tiny._dump({'logit_gap_max': LIMIT}, tree, 'benchmark', 'limits',
                     CELL + '.json')
    path = os.path.join(tree, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append(
        {'name': 'g4h-tiny', 'source': 'toy', 'reduced': [], 'why': 'toy',
         'file': 'benchmark/configs/g4h-tiny.json'})
    bench['workloads'].append(
        {'name': CELL, 'config': 'g4h-tiny', 'traffic': 'g4h-sat-tiny',
         'chips': 1, 'why': 'toy'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if m['name'] == 'serve_tokens_per_s' or m['name'].endswith('.g4h'):
            m['workloads'].append(CELL)
    bench_tiny._dump(bench, tree, 'BENCHMARK.json')
    return tree
