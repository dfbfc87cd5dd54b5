"""bf16 training-path tests (the TPU-native precision; reference analog:
fp16 training in tests/python/train/test_dtype.py).

Round-1 regression: cotangents crossing TapeNode boundaries in the loss's
promoted dtype (f32) broke conv/dense backward under net.cast('bfloat16').
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def _conv_bn_net():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
                nn.Activation('relu'), nn.GlobalAvgPool2D(), nn.Flatten(),
                nn.Dense(10))
    return net


@pytest.mark.parametrize('hybridize', [False, True])
def test_bf16_conv_bn_dense_backward(hybridize):
    net = _conv_bn_net()
    net.initialize(mx.init.Xavier())
    net.cast('bfloat16')
    if hybridize:
        net.hybridize()
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    x = nd.array(np.random.randn(4, 3, 8, 8), dtype='bfloat16')
    y = nd.array(np.random.randint(0, 10, (4,)))
    with autograd.record():
        loss = L(net(x), y)
    loss.backward()
    for p in net.collect_params().values():
        if p.grad_req != 'null':
            g = p.grad()
            if 'gamma' in p.name or 'beta' in p.name:
                # BatchNorm affine params stay float32 under
                # net.cast('bfloat16') — the fp32-stat contract
                # (docs/PRECISION.md; BatchNorm.cast)
                assert str(g.dtype) == 'float32'
            else:
                assert g.dtype == np.dtype('bfloat16') or \
                    str(g.dtype) == 'bfloat16'
            assert np.isfinite(g.asnumpy().astype(np.float32)).all()


def test_bf16_train_step_decreases_loss():
    net = _conv_bn_net()
    net.initialize(mx.init.Xavier())
    net.cast('bfloat16')
    net.hybridize()
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1, 'momentum': 0.9})
    x = nd.array(np.random.randn(16, 3, 8, 8), dtype='bfloat16')
    y = nd.array(np.random.randint(0, 10, (16,)))
    first = None
    for _ in range(10):
        with autograd.record():
            loss = L(net(x), y)
        loss.backward()
        trainer.step(16)
        cur = float(loss.mean().asscalar())
        if first is None:
            first = cur
    assert cur < first


def test_bf16_dense_grad_matches_f32():
    """bf16 gradients should track f32 gradients to bf16 precision."""
    w = np.random.randn(8, 8).astype(np.float32)
    x_np = np.random.randn(4, 8).astype(np.float32)
    grads = {}
    for dt in ['float32', 'bfloat16']:
        net = nn.Dense(8)
        net.initialize(mx.init.Constant(0.0))
        # force identical weights
        _ = net(nd.array(x_np, dtype=dt))
        net.weight.set_data(nd.array(w, dtype=dt))
        with autograd.record():
            out = net(nd.array(x_np, dtype=dt))
            loss = (out * out).sum()
        loss.backward()
        grads[dt] = net.weight.grad().asnumpy().astype(np.float32)
    np.testing.assert_allclose(grads['bfloat16'], grads['float32'],
                               rtol=0.1, atol=0.5)
