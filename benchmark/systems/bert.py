"""The program under test for the ``bert`` family: the model zoo's BERT
under ``ParallelTrainer``, built the way ``bench.py:bench_bert`` builds it,
with the benchmark's weights set into its parameters.

Everything here is a call into the program; nothing of it is read by the
reference. ``LEAF`` maps the program's parameter names (after the block's
own prefix) to the reference's leaf names.
"""
import re

import numpy as np

_LAYER = {'attn_qkv_weight': 'qkv_w', 'attn_qkv_bias': 'qkv_b',
          'attn_out_weight': 'out_w', 'attn_out_bias': 'out_b',
          'ln_attn_gamma': 'ln1_g', 'ln_attn_beta': 'ln1_b',
          'ffn_ffn1_weight': 'ffn1_w', 'ffn_ffn1_bias': 'ffn1_b',
          'ffn_ffn2_weight': 'ffn2_w', 'ffn_ffn2_bias': 'ffn2_b',
          'ln_ffn_gamma': 'ln2_g', 'ln_ffn_beta': 'ln2_b'}
_TOP = {'word_weight': 'word', 'type_weight': 'type', 'pos_weight': 'pos',
        'emb_ln_gamma': 'emb_ln_g', 'emb_ln_beta': 'emb_ln_b',
        'pooler_weight': 'pool_w', 'pooler_bias': 'pool_b',
        'dec_weight': 'dec_w', 'dec_bias': 'dec_b',
        'dec_ln_gamma': 'dec_ln_g', 'dec_ln_beta': 'dec_ln_b',
        'decoder_bias': 'mlm_b', 'nsp_weight': 'nsp_w', 'nsp_bias': 'nsp_b'}


def leaf_name(param_name, prefix):
    """Reference leaf for a program parameter, or None for a parameter the
    pre-training graph never reads (the cross-attention projections)."""
    tail = param_name[len(prefix):]
    m = re.match(r'enc_layer(\d+)_(.+)$', tail)
    if m:
        leaf = _LAYER.get(m.group(2))
        return leaf and 'l%s.%s' % (m.group(1), leaf)
    return _TOP.get(tail)


class Trainer:
    """One compiled step with its state. ``step(batch)`` is the call the
    window makes; ``state()`` reads parameters and Adam's first moment
    back through the trainer's public ``snapshot()``."""

    def __init__(self, cfg, traffic, weights, devices):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon, nd, parallel
        from mxnet_tpu.gluon.block import ensure_initialized
        from mxnet_tpu.gluon.model_zoo import bert as bert_zoo
        from mxnet_tpu.ndarray import NDArray
        # gluon's initializers draw from numpy's global generator (PR 21)
        np.random.seed(0)
        mx.random.seed(0)
        self._nd = nd
        net = bert_zoo.get_bert(
            'bert_12_768_12', vocab_size=cfg['vocab_size'],
            max_length=cfg['max_position_embeddings'],
            units=cfg['hidden_size'], hidden_size=cfg['intermediate_size'],
            num_layers=cfg['num_hidden_layers'],
            num_heads=cfg['num_attention_heads'],
            dropout=cfg['hidden_dropout_prob'])
        net.initialize(mx.init.Zero())
        net.hybridize(static_alloc=True, static_shape=True)
        vocab = cfg['vocab_size']
        xent = gluon.loss.SoftmaxCrossEntropyLoss()

        def pretrain_loss(outs, labels):
            _, _, mlm_s, nsp_s = outs
            my, ny = labels
            return xent(mlm_s.reshape((-1, vocab)),
                        my.reshape((-1,))).mean() + xent(nsp_s, ny).mean()

        self.names = []
        for name, param in net.collect_params().items():
            leaf = leaf_name(name, net.prefix)
            self.names.append(leaf)
            if leaf is not None:
                param.set_data(NDArray(weights[leaf]))
        # shapes that gluon defers are settled by one eager pass; a single
        # short row settles them as well as a whole batch does
        probe = self._feed({'ids': np.zeros((1, 8), 'int32'),
                            'types': np.zeros((1, 8), 'int32'),
                            'valid': np.full((1,), 8, 'int32'),
                            'positions': np.zeros((1, 1), 'int32'),
                            'mlm_labels': np.zeros((1, 1), 'int32'),
                            'nsp_labels': np.zeros((1,), 'int32')})
        ensure_initialized(net, *probe[0])
        opt = cfg['optimizer']
        mesh = parallel.create_mesh({'dp': len(devices)}, devices=devices)
        self.pt = parallel.ParallelTrainer(
            net, pretrain_loss, opt['name'],
            {k: opt[k] for k in ('learning_rate', 'wd', 'beta1', 'beta2',
                                 'epsilon')},
            mesh, amp={'bfloat16': 'bf16'}[cfg['precision']['compute']])
        self.net = net

    def _feed(self, batch):
        f = lambda k, t: self._nd.array(batch[k].astype(t))    # noqa: E731
        return ([f('ids', 'float32'), f('types', 'float32'),
                 f('valid', 'float32'), f('positions', 'float32')],
                [f('mlm_labels', 'float32'), f('nsp_labels', 'float32')])

    def step(self, batch):
        """One fused train step; returns the loss still on the device."""
        x, y = self._feed(batch)
        return self.pt.step(x, y)

    @staticmethod
    def wait(loss):
        loss.wait_to_read()
        return float(loss.asnumpy())

    def state(self):
        """Leaf name -> (parameter, Adam first moment) as numpy arrays."""
        snap = self.pt.snapshot()
        leaves = iter(snap['leaves'])
        out = {}
        for leaf, w in zip(self.names, snap['params']):
            mean, _var = next(leaves), next(leaves)
            if leaf is not None:
                out[leaf] = (np.asarray(w, 'float32'),
                             np.asarray(mean, 'float32'))
        return out

    def close(self):
        self.pt = self.net = None
