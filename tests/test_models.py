"""Model zoo: LeNet converges on synthetic MNIST (SURVEY §4)."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer as opt, jit
from paddle_tpu.models import LeNet


def synthetic_mnist(n=256, seed=0):
    """Class-separable synthetic digits: class k gets a bright kxk block."""
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 1, 28, 28).astype("f4") * 0.1
    y = rng.randint(0, 10, size=(n,))
    for i in range(n):
        k = y[i]
        r, c = divmod(k, 4)
        x[i, 0, 3 + r * 8:9 + r * 8, 3 + c * 6:9 + c * 6] += 1.0
    return x, y.astype("i4")


def test_lenet_converges():
    pt.seed(0)
    model = LeNet()
    o = opt.Adam(learning_rate=1e-3, parameters=model.parameters())
    x, y = synthetic_mnist(256)

    def step(xb, yb):
        logits = model(xb)
        loss = pt.nn.functional.cross_entropy(logits, yb)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o])
    first = None
    for epoch in range(6):
        for i in range(0, 256, 64):
            loss = fn(pt.to_tensor(x[i:i + 64]), pt.to_tensor(y[i:i + 64]))
    first = first or float(loss.numpy())
    # accuracy after training
    model.eval()
    logits = model(pt.to_tensor(x))
    acc = float((logits.argmax(-1).numpy() == y).mean())
    assert acc > 0.9, f"LeNet failed to fit synthetic MNIST: acc={acc}"


def test_resnet50_forward_backward():
    from paddle_tpu.models.resnet import resnet50, resnet18
    m = resnet18(num_classes=10)
    x = pt.to_tensor(np.random.randn(2, 3, 32, 32).astype("f4"))
    y = pt.to_tensor(np.array([1, 2]))
    loss = pt.nn.functional.cross_entropy(m(x), y)
    loss.backward()
    grads = [p for p in m.parameters() if p.grad is not None]
    assert len(grads) == len([p for p in m.parameters()
                              if not p.stop_gradient])
    m50 = resnet50(num_classes=10)
    assert m50(x).shape == [2, 10]
    # param count sanity: resnet50 ~25.5M for 1000 classes
    n = sum(p.size for p in resnet50(num_classes=1000).parameters())
    assert 25_000_000 < n < 26_000_000


def test_bert_tiny_forward_backward():
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    cfg = BertConfig.tiny()
    m = BertForPretraining(cfg)
    b, s = 2, 16
    ids = pt.to_tensor(np.random.randint(0, cfg.vocab_size, (b, s)))
    tt = pt.to_tensor(np.zeros((b, s), "i4"))
    mask = pt.to_tensor(np.ones((b, s), "i4"))
    mlm_labels = pt.to_tensor(np.where(np.random.rand(b, s) < 0.15,
                                       np.random.randint(0, cfg.vocab_size,
                                                         (b, s)), -1))
    nsp_labels = pt.to_tensor(np.array([0, 1]))
    logits, nsp = m(ids, tt, mask)
    assert logits.shape == [b, s, cfg.vocab_size]
    loss = m.loss(logits, nsp, mlm_labels, nsp_labels)
    loss.backward()
    assert m.bert.embeddings.word_embeddings.weight.grad is not None


def test_transformer_seq2seq():
    from paddle_tpu.models.transformer import Transformer
    m = Transformer(src_vocab_size=100, tgt_vocab_size=100, d_model=32,
                    num_heads=4, num_encoder_layers=2, num_decoder_layers=2,
                    d_ff=64, max_length=32)
    src = pt.to_tensor(np.random.randint(1, 100, (2, 10)))
    tgt = pt.to_tensor(np.random.randint(1, 100, (2, 8)))
    mask = pt.to_tensor(np.ones((2, 10), "i4"))
    logits = m(src, tgt, mask)
    assert logits.shape == [2, 8, 100]
    labels = pt.to_tensor(np.random.randint(1, 100, (2, 8)))
    loss = m.loss(logits, labels)
    loss.backward()
    assert m.src_embed.weight.grad is not None


def test_ctr_models():
    from paddle_tpu.models.ctr import WideDeep, DeepFM
    ids = pt.to_tensor(np.random.randint(0, 1000, (4, 26)))
    dense = pt.to_tensor(np.random.rand(4, 13).astype("f4"))
    label = pt.to_tensor(np.array([0, 1, 1, 0]))
    for cls in (WideDeep, DeepFM):
        m = cls(sparse_feature_number=1000)
        logit = m(ids, dense)
        assert logit.shape == [4, 1]
        loss = m.loss(logit, label)
        loss.backward()


def test_word2vec():
    from paddle_tpu.models.word2vec import SkipGram
    m = SkipGram(vocab_size=100, embedding_dim=16)
    center = pt.to_tensor(np.random.randint(0, 100, (8,)))
    context = pt.to_tensor(np.random.randint(0, 100, (8,)))
    loss = m.train_batch_loss(center, context)
    loss.backward()
    assert m.emb_in.weight.grad is not None


@pytest.mark.slow  # 52-54 s alone, 67 s inside the whole tier-1 run (PR 29)
def test_vgg_mobilenet_smoke():
    from paddle_tpu.models.vgg import vgg16
    from paddle_tpu.models.mobilenet import MobileNetV1, MobileNetV2
    x = pt.to_tensor(np.random.randn(1, 3, 64, 64).astype("f4"))
    assert vgg16(num_classes=5, image_size=64)(x).shape == [1, 5]
    assert MobileNetV1(num_classes=5)(x).shape == [1, 5]
    assert MobileNetV2(num_classes=5)(x).shape == [1, 5]


def test_resnet_nhwc_matches_nchw():
    """data_format='NHWC' plumbs through stem/blocks/pools and matches
    the NCHW model in eval mode (weights stay OIHW — layout-independent
    state dicts)."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.models.resnet import resnet18

    np.random.seed(0)
    x = np.random.rand(2, 3, 32, 32).astype("f4")
    xh = np.transpose(x, (0, 2, 3, 1)).copy()
    pt.seed(0)
    m_nchw = resnet18(num_classes=10)
    pt.seed(0)
    m_nhwc = resnet18(num_classes=10, data_format="NHWC")
    m_nchw.eval()
    m_nhwc.eval()
    np.testing.assert_allclose(
        m_nhwc(pt.to_tensor(xh)).numpy(),
        m_nchw(pt.to_tensor(x)).numpy(), atol=1e-4)
    # identical state dicts regardless of layout
    for (k1, v1), (k2, v2) in zip(sorted(m_nchw.state_dict().items()),
                                  sorted(m_nhwc.state_dict().items())):
        assert k1 == k2 and v1.shape == v2.shape


def test_se_resnext50_forward_and_grads():
    """SE-ResNeXt (grouped convs + SE gates) trains a step; the SE gate
    actually modulates (zeroing excite bias shifts outputs)."""
    from paddle_tpu.models.se_resnext import se_resnext50
    pt.seed(0)
    m = se_resnext50(num_classes=10)
    x = pt.to_tensor(np.random.RandomState(0).rand(2, 3, 48, 48)
                     .astype("f4"))
    y = pt.to_tensor(np.array([1, 7], "i4"))
    logits = m(x)
    assert tuple(logits.shape) == (2, 10)
    loss = nn.functional.cross_entropy(logits, y)
    loss.backward()
    o = opt.Momentum(learning_rate=0.05, momentum=0.9,
                     parameters=m.parameters())
    o.step()
    o.clear_grad()
    loss2 = nn.functional.cross_entropy(m(x), y)
    assert float(loss2.numpy()) < float(loss.numpy())
    # a grouped conv exists with cardinality 32
    from paddle_tpu.models.se_resnext import SEResNeXtBottleneck
    blk = next(l for l in m.sublayers()
               if isinstance(l, SEResNeXtBottleneck))
    assert blk.conv1._attrs["groups"] == 32


def test_resnet_nhwc_pallas_bn_matches_nchw():
    """NHWC resnet == NCHW resnet on transposed input (same seed, same
    params): the layout knob changes memory order only. Also asserts
    the fused Pallas BN path (interpret mode) agrees end-to-end.
    Tolerance is loose (~1e-2): conv reduction order differs per
    layout and 18 BN divisions amplify it."""
    import numpy as np
    from paddle_tpu.models.resnet import resnet18
    from paddle_tpu.ops import pallas as P

    x = np.random.RandomState(0).rand(2, 3, 32, 32).astype("f4")

    def logits(fmt, pallas_bn=False):
        P.configure(batch_norm=pallas_bn)
        try:
            pt.seed(11)
            m = resnet18(num_classes=8, data_format=fmt)
            xin = x if fmt == "NCHW" else x.transpose(0, 2, 3, 1)
            return m(pt.to_tensor(xin)).numpy()
        finally:
            P.configure(batch_norm=None)

    a = logits("NCHW")
    b = logits("NHWC")
    np.testing.assert_allclose(b, a, rtol=3e-2, atol=3e-3)
    c = logits("NHWC", pallas_bn=True)
    np.testing.assert_allclose(c, a, rtol=3e-2, atol=3e-3)
