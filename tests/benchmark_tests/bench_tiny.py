"""A tree of data files at toy sizes for the CPU tests: the benchmark's own
metric files and peaks, with tiny configurations, traffic, limits and a
``BENCHMARK.json`` of their own. The harness is pointed at it with
``run.execute(..., root=tree)``."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TRAIN_LIMITS = {'loss_gap_step1': 1e-3, 'loss_gap_step2': 1e-3,
                'loss_gap_step3': 1e-3, 'grad_norm_gap': 0.15,
                'update_norm_gap': 0.15}
LENS = {'prompt_len': {'median': 12, 'sigma': 0.5, 'min': 4, 'max': 30},
        'output_len': {'median': 8, 'sigma': 0.3, 'min': 4, 'max': 16}}


def _dump(obj, *parts):
    path = os.path.join(*parts)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'w') as f:
        json.dump(obj, f)


def build(tree):
    """Write the toy tree under ``tree`` and return its path."""
    tree = str(tree)
    src = os.path.join(REPO, 'benchmark')
    shutil.copytree(os.path.join(src, 'metrics'),
                    os.path.join(tree, 'benchmark', 'metrics'))
    shutil.copy(os.path.join(src, 'peaks.json'),
                os.path.join(tree, 'benchmark', 'peaks.json'))
    with open(os.path.join(src, 'configs', 'bert-base.json')) as f:
        bert = json.load(f)
    bert.update(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=64,
                max_position_embeddings=32)
    _dump(bert, tree, 'benchmark', 'configs', 'bert-tiny.json')
    with open(os.path.join(src, 'configs', 'gpt1-117m.json')) as f:
        gpt = json.load(f)
    gpt.update(n_layer=2, n_embd=32, n_head=4, n_positions=64, n_ctx=64,
               vocab_size=97, intermediate_size=64,
               initializer_range=0.08)
    gpt['deployment'].update(slots=4, page_size=8, pages=33,
                             prefill_buckets=[16, 32], max_queue=16,
                             max_new_tokens=16, prefill_interleave=2)
    _dump(gpt, tree, 'benchmark', 'configs', 'gpt-tiny.json')
    _dump({'kind': 'train', 'batch': 8, 'seq_len': 16, 'masked': 4,
           'min_valid_share': 0.5, 'distinct_batches': 4,
           'followed_steps': 3, 'trace_seconds': 1.0},
          tree, 'benchmark', 'traffic', 'pretrain-tiny.json')
    common = dict(population=32, ramp_seconds=0.3, grace_seconds=20.0,
                  checked_requests=40, trace_seconds=1.0, **LENS)
    _dump(dict(loop='closed', clients=6, stream=True, **common),
          tree, 'benchmark', 'traffic', 'sat-tiny.json')
    _dump(dict(loop='open', rate_per_s=12.0, **common),
          tree, 'benchmark', 'traffic', 'chat-tiny.json')
    _dump(TRAIN_LIMITS, tree, 'benchmark', 'limits',
          'bert-tiny-pretrain.json')
    for cell in ('gpt-tiny-sat', 'gpt-tiny-chat'):
        _dump({'logit_gap_max': 1e-3}, tree, 'benchmark', 'limits',
              cell + '.json')
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    rename = {'bert-base-pretrain': 'bert-tiny-pretrain',
              'gpt1-batch-saturated': 'gpt-tiny-sat',
              'gpt1-chat-steady': 'gpt-tiny-chat'}
    bench['configs'] = [
        {'name': 'bert-tiny', 'source': 'toy', 'reduced': [], 'why': 'toy',
         'file': 'benchmark/configs/bert-tiny.json'},
        {'name': 'gpt-tiny', 'source': 'toy', 'reduced': [], 'why': 'toy',
         'file': 'benchmark/configs/gpt-tiny.json'}]
    bench['workloads'] = [
        {'name': 'bert-tiny-pretrain', 'config': 'bert-tiny',
         'traffic': 'pretrain-tiny', 'chips': 1, 'why': 'toy'},
        {'name': 'gpt-tiny-sat', 'config': 'gpt-tiny',
         'traffic': 'sat-tiny', 'chips': 1, 'why': 'toy'},
        {'name': 'gpt-tiny-chat', 'config': 'gpt-tiny',
         'traffic': 'chat-tiny', 'chips': 1, 'why': 'toy'}]
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'workloads' in m:
            m['workloads'] = [rename[w] for w in m['workloads']
                              if w in rename]
    _dump(bench, tree, 'BENCHMARK.json')
    return tree


def context(tree, cell, seed=5, seconds=1.0):
    """A harness context for ``cell`` of the toy tree, on whatever backend
    the tests run on."""
    from benchmark import run
    bench = run.load_json(tree, 'BENCHMARK.json')
    ctx = run.Context(tree, bench, run.find_cell(bench, cell), seed, seconds,
                      0, require_chip=False)
    ctx.attach_devices()
    return ctx
