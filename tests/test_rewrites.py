"""Tests for the frontend-periphery components (metrics, schedulers,
samplers, naming, callbacks, bucketing iter, model zoo) — reference
models: tests/python/unittest/test_metric.py, test_gluon_data.py,
test_lr_scheduler cases inside test_optimizer.py."""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.data import (BatchSampler, RandomSampler,
                                  SequentialSampler, FilterSampler)
from mxnet_tpu.gluon import model_zoo


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_accuracy():
    m = mx.metric.Accuracy()
    pred = nd.array([[0.3, 0.7], [0.9, 0.1], [0.4, 0.6]])
    label = nd.array([1, 0, 0])
    m.update([label], [pred])
    assert m.get()[1] == pytest.approx(2.0 / 3)


def test_top_k_accuracy():
    m = mx.metric.TopKAccuracy(top_k=2)
    pred = nd.array([[0.1, 0.5, 0.4], [0.6, 0.3, 0.1]])
    label = nd.array([2, 1])
    m.update([label], [pred])
    assert m.get()[1] == pytest.approx(1.0)   # both in top-2


def test_f1_against_manual_confusion():
    # tp=2, fp=1, fn=1, tn=1 -> precision 2/3, recall 2/3, f1 2/3
    pred = nd.array([[0.2, 0.8], [0.2, 0.8], [0.2, 0.8],
                     [0.8, 0.2], [0.8, 0.2]])
    label = nd.array([1, 1, 0, 1, 0])
    m = mx.metric.F1(average='micro')
    m.update([label], [pred])
    assert m.get()[1] == pytest.approx(2 / 3, abs=1e-6)


def test_mcc_against_manual():
    pred = nd.array([[0.2, 0.8], [0.2, 0.8], [0.2, 0.8],
                     [0.8, 0.2], [0.8, 0.2]])
    label = nd.array([1, 1, 0, 1, 0])
    m = mx.metric.MCC(average='micro')
    m.update([label], [pred])
    tp, fp, fn, tn = 2., 1., 1., 1.
    expect = (tp * tn - fp * fn) / np.sqrt(
        (tp + fp) * (tp + fn) * (tn + fp) * (tn + fn))
    assert m.get()[1] == pytest.approx(expect, abs=1e-6)


def test_mae_mse_rmse():
    label = nd.array([1.0, 2.0, 3.0])
    pred = nd.array([1.5, 2.0, 2.0])
    mae = mx.metric.MAE()
    mae.update([label], [pred])
    assert mae.get()[1] == pytest.approx(0.5)
    mse = mx.metric.MSE()
    mse.update([label], [pred])
    assert mse.get()[1] == pytest.approx((0.25 + 0 + 1) / 3)


def test_pearson_micro_matches_corrcoef():
    rs = np.random.RandomState(0)
    l = rs.randn(40)
    p = 0.7 * l + 0.3 * rs.randn(40)
    m = mx.metric.PearsonCorrelation(average='micro')
    for i in range(0, 40, 10):
        m.update([nd.array(l[i:i + 10])], [nd.array(p[i:i + 10])])
    assert m.get()[1] == pytest.approx(np.corrcoef(p, l)[0, 1], abs=1e-6)
    assert m.get_global()[1] == pytest.approx(np.corrcoef(p, l)[0, 1],
                                              abs=1e-6)
    m.reset()
    m.update([nd.array(l)], [nd.array(p)])
    assert m.get()[1] == pytest.approx(np.corrcoef(p, l)[0, 1], abs=1e-6)


def test_perplexity():
    m = mx.metric.Perplexity(ignore_label=None)
    pred = nd.array([[0.5, 0.5], [0.9, 0.1]])
    label = nd.array([0, 0])
    m.update([label], [pred])
    expect = np.exp(-(np.log(0.5) + np.log(0.9)) / 2)
    assert m.get()[1] == pytest.approx(expect, rel=1e-5)


def test_custom_metric_tuple_and_scalar():
    cm = mx.metric.CustomMetric(lambda l, p: (np.abs(l - p).sum(), l.size))
    cm.update([nd.array([1.0, 2.0])], [nd.array([2.0, 2.0])])
    assert cm.get()[1] == pytest.approx(0.5)
    cm2 = mx.metric.CustomMetric(lambda l, p: float(np.abs(l - p).mean()))
    cm2.update([nd.array([1.0, 2.0])], [nd.array([2.0, 2.0])])
    assert cm2.get()[1] == pytest.approx(0.5)


def test_composite_metric():
    comp = mx.metric.CompositeEvalMetric([mx.metric.Accuracy(),
                                          mx.metric.MAE()])
    pred = nd.array([[0.3, 0.7]])
    comp.update([nd.array([1])], [pred])
    names, values = comp.get()
    assert len(names) == 2


# ---------------------------------------------------------------------------
# lr schedulers
# ---------------------------------------------------------------------------

def test_factor_scheduler():
    s = mx.lr_scheduler.FactorScheduler(step=10, factor=0.5, base_lr=1.0)
    assert s(1) == pytest.approx(1.0)
    assert s(10) == pytest.approx(1.0)     # boundary keeps old lr
    assert s(11) == pytest.approx(0.5)
    assert s(21) == pytest.approx(0.25)
    # stop floor
    assert s(1000) >= 1e-8


def test_multifactor_scheduler():
    s = mx.lr_scheduler.MultiFactorScheduler(step=[5, 8], factor=0.1,
                                             base_lr=1.0)
    assert s(5) == pytest.approx(1.0)
    assert s(6) == pytest.approx(0.1)
    assert s(9) == pytest.approx(0.01)


def test_poly_and_cosine_schedulers():
    p = mx.lr_scheduler.PolyScheduler(max_update=100, base_lr=1.0, pwr=2,
                                      final_lr=0.0)
    assert p(0) == pytest.approx(1.0)
    assert p(50) == pytest.approx(0.25)
    assert p(100) == pytest.approx(0.0)
    c = mx.lr_scheduler.CosineScheduler(max_update=100, base_lr=1.0,
                                        final_lr=0.0)
    assert c(0) == pytest.approx(1.0)
    assert c(50) == pytest.approx(0.5)
    assert c(100) == pytest.approx(0.0, abs=1e-9)


def test_warmup():
    s = mx.lr_scheduler.CosineScheduler(max_update=100, base_lr=1.0,
                                        warmup_steps=10,
                                        warmup_begin_lr=0.0)
    assert s(0) == pytest.approx(0.0)
    assert s(5) == pytest.approx(0.5)
    assert s(10) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------

def test_sequential_and_random_sampler():
    assert list(SequentialSampler(5)) == [0, 1, 2, 3, 4]
    assert list(SequentialSampler(3, start=7)) == [7, 8, 9]
    got = sorted(RandomSampler(6))
    assert got == list(range(6))


def test_filter_sampler():
    data = [0, 1, 2, 3, 4, 5]
    s = FilterSampler(lambda x: x % 2 == 0, data)
    assert list(s) == [0, 2, 4]
    assert len(s) == 3


def test_batch_sampler_modes():
    base = SequentialSampler(7)
    keep = BatchSampler(base, 3, 'keep')
    assert [len(b) for b in keep] == [3, 3, 1]
    assert len(keep) == 3
    discard = BatchSampler(base, 3, 'discard')
    assert [len(b) for b in discard] == [3, 3]
    assert len(discard) == 2
    roll = BatchSampler(base, 3, 'rollover')
    assert [len(b) for b in roll] == [3, 3]
    # the leftover index rolls into the next epoch
    batches = list(roll)
    assert batches[0] == [6, 0, 1]
    with pytest.raises(ValueError):
        BatchSampler(base, 3, 'bogus')


# ---------------------------------------------------------------------------
# naming
# ---------------------------------------------------------------------------

def test_name_manager_scoping():
    with mx.name.NameManager() as nm:
        assert nm.get(None, 'conv') == 'conv0'
        assert nm.get(None, 'conv') == 'conv1'
        assert nm.get('explicit', 'conv') == 'explicit'
        with mx.name.Prefix('outer_'):
            assert mx.name.NameManager.current.get(None, 'fc') == \
                'outer_fc0'
        assert nm.get(None, 'fc') == 'fc0'


# ---------------------------------------------------------------------------
# callbacks
# ---------------------------------------------------------------------------

class _Param:
    def __init__(self, epoch, nbatch, metric=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = metric


def test_speedometer_logs(caplog):
    sp = mx.callback.Speedometer(batch_size=4, frequent=2,
                                 auto_reset=False)
    m = mx.metric.Accuracy()
    m.update([nd.array([1])], [nd.array([[0.2, 0.8]])])
    with caplog.at_level(logging.INFO):
        for nb in range(1, 5):
            sp(_Param(0, nb, m))
    assert any('samples/sec' in r.message for r in caplog.records)


def test_progress_bar_logs(caplog):
    bar = mx.callback.ProgressBar(total=10, length=10)
    with caplog.at_level(logging.INFO):
        bar(_Param(0, 5))
    assert any('=' in r.message for r in caplog.records)


def test_log_train_metric(caplog):
    cb = mx.callback.log_train_metric(1)
    m = mx.metric.Accuracy()
    m.update([nd.array([1])], [nd.array([[0.2, 0.8]])])
    with caplog.at_level(logging.INFO):
        cb(_Param(0, 1, m))
    assert any('Train-accuracy' in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# bucketing iterator
# ---------------------------------------------------------------------------

def test_encode_sentences_builds_vocab():
    sents = [['a', 'b'], ['b', 'c', 'a']]
    enc, vocab = mx.rnn.encode_sentences(sents, start_label=1)
    assert enc[0] == [vocab['a'], vocab['b']]
    assert len(set(vocab.values())) == len(vocab)


def test_bucket_sentence_iter():
    rs = np.random.RandomState(0)
    sents = [list(rs.randint(1, 20, size=n))
             for n in rs.randint(2, 9, size=64)]
    it = mx.rnn.BucketSentenceIter(sents, batch_size=4, buckets=[4, 8],
                                   invalid_label=0)
    batch = it.next()
    assert batch.data[0].shape[0] == 4
    assert batch.bucket_key in (4, 8)
    d = batch.data[0].asnumpy()
    l = batch.label[0].asnumpy()
    # label is data shifted one step left
    np.testing.assert_allclose(l[:, :-1], d[:, 1:])
    assert (l[:, -1] == 0).all()
    n_batches = 1
    while True:
        try:
            it.next()
            n_batches += 1
        except StopIteration:
            break
    it.reset()
    assert it.curr_idx == 0


def test_bucket_sentence_iter_time_major():
    sents = [[1, 2, 3], [4, 5], [1, 2], [3, 4]]
    it = mx.rnn.BucketSentenceIter(sents, batch_size=2, buckets=[4],
                                   invalid_label=0, layout='TN')
    batch = it.next()
    assert batch.data[0].shape == (4, 2)


# ---------------------------------------------------------------------------
# model zoo (rewritten nets still build and classify)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('factory,size', [
    ('alexnet', 224), ('squeezenet1_0', 224), ('squeezenet1_1', 224),
    ('vgg11', 32), ('vgg13_bn', 32),
    ('resnet18_v1', 32), ('resnet18_v2', 32),
    ('resnet50_v1', 32), ('resnet50_v2', 32),
    ('densenet121', 224), ('mobilenet0_25', 224),
    ('mobilenet_v2_0_25', 224),
])
def test_model_zoo_forward(factory, size):
    net = getattr(model_zoo.vision, factory)(classes=10)
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).randn(1, 3, size, size)
                 .astype('float32'))
    out = net(x)
    assert out.shape == (1, 10)
    assert np.isfinite(out.asnumpy()).all()


def test_inception_v3_forward():
    net = model_zoo.vision.inception_v3(classes=7)
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).randn(1, 3, 299, 299)
                 .astype('float32'))
    assert net(x).shape == (1, 7)


def test_resnet_v1_vs_v2_parameter_counts_differ_only_in_norms():
    def count(net):
        return sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    n1 = model_zoo.vision.resnet18_v1(classes=10)
    n1.initialize(mx.init.Xavier())
    x = nd.array(np.zeros((1, 3, 32, 32), 'float32'))
    n1(x)
    n2 = model_zoo.vision.resnet18_v2(classes=10)
    n2.initialize(mx.init.Xavier())
    n2(x)
    # same conv budget; small BN bookkeeping differences only
    assert abs(count(n1) - count(n2)) / count(n1) < 0.02


def test_conv_internal_nhwc_matches_nchw():
    """The channels-last internal conv path (used on accelerators) is
    numerically identical to the NCHW path."""
    from mxnet_tpu.ops import nn as nn_ops
    from mxnet_tpu.ndarray.ndarray import invoke
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(2, 3, 16, 16).astype('float32'))
    w = nd.array(rng.randn(8, 3, 3, 3).astype('float32'))
    b = nd.array(rng.randn(8).astype('float32'))
    attrs = dict(kernel=(3, 3), pad=(1, 1), stride=(2, 2), num_filter=8)
    saved = dict(nn_ops._CONV_INTERNAL)
    try:
        nn_ops._CONV_INTERNAL['nhwc'] = False
        ref = invoke('Convolution', [x, w, b], attrs).asnumpy()
        nn_ops._CONV_INTERNAL['nhwc'] = True
        got = invoke('Convolution', [x, w, b], attrs).asnumpy()
    finally:
        nn_ops._CONV_INTERNAL.update(saved)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)

    # grouped conv takes the same branch
    xg = nd.array(rng.randn(2, 4, 8, 8).astype('float32'))
    wg = nd.array(rng.randn(8, 2, 3, 3).astype('float32'))
    ag = dict(kernel=(3, 3), pad=(1, 1), num_filter=8, num_group=2,
              no_bias=True)
    try:
        nn_ops._CONV_INTERNAL['nhwc'] = False
        ref = invoke('Convolution', [xg, wg], ag).asnumpy()
        nn_ops._CONV_INTERNAL['nhwc'] = True
        got = invoke('Convolution', [xg, wg], ag).asnumpy()
    finally:
        nn_ops._CONV_INTERNAL.update(saved)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model_store: offline pretrained-weight protocol
# ---------------------------------------------------------------------------

def test_model_store_seed_fixture_happy_path(tmp_path):
    """create_seed_fixture stages deterministic weights that
    pretrained=True then resolves offline."""
    from mxnet_tpu.gluon.model_zoo import model_store
    root = str(tmp_path)
    path = model_store.create_seed_fixture('squeezenet1.0', root=root,
                                           classes=10)
    assert path.endswith('squeezenet1.0.params')
    net = model_zoo.vision.get_model('squeezenet1.0', pretrained=True,
                                     root=root, classes=10)
    x = nd.array(np.random.RandomState(0).randn(1, 3, 224, 224)
                 .astype('float32'))
    out = net(x)
    assert out.shape == (1, 10)
    # determinism: same seed -> byte-identical fixture
    again = model_store.create_seed_fixture('squeezenet1.0', root=root,
                                            classes=10)
    net2 = model_zoo.vision.get_model('squeezenet1.0', pretrained=True,
                                      root=root, classes=10)
    np.testing.assert_allclose(net2(x).asnumpy(), out.asnumpy(),
                               rtol=1e-6, atol=1e-6)
    assert again == path


def test_model_store_missing_and_corrupt(tmp_path):
    from mxnet_tpu.gluon.model_zoo import model_store
    root = str(tmp_path)
    with pytest.raises(RuntimeError, match='not found'):
        model_store.get_model_file('resnet18_v1', root=root)
    # a pin-named file whose contents do not match the published sha1
    bogus = tmp_path / ('resnet18_v1-%s.params'
                        % model_store.short_hash('resnet18_v1'))
    bogus.write_bytes(b'not really weights')
    with pytest.raises(ValueError, match='sha1'):
        model_store.get_model_file('resnet18_v1', root=root)
    # unknown names have no pin at all
    with pytest.raises(ValueError, match='not available'):
        model_store.short_hash('made_up_net')
