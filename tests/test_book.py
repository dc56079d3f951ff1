"""The reference's book chapters, end-to-end through the fluid facade
(reference: python/paddle/fluid/tests/book/*.py). Each test builds the
chapter's model in static mode (or dygraph where the book does), trains a
few steps on synthetic data, and asserts the loss drops — the ported-user
experience check."""
import numpy as np

import paddle_tpu as pt
import paddle_tpu.fluid as fluid

layers = fluid.layers


def _run_static(build, feeds, steps=25, lr=0.1, opt_cls=None):
    """Build a program with `build()` -> loss, train `steps` on `feeds`."""
    from paddle_tpu import static, optimizer as opt
    pt.enable_static()
    try:
        main = static.Program()
        startup = static.Program()
        with static.program_guard(main, startup):
            loss = build()
            (opt_cls or opt.SGD)(learning_rate=lr).minimize(loss)
        exe = static.Executor()
        exe.run(startup)
        losses = []
        for _ in range(steps):
            (lv,) = exe.run(main, feed=feeds, fetch_list=[loss])
            losses.append(float(np.asarray(lv)))
        return losses
    finally:
        pt.disable_static()


def test_fit_a_line():
    """reference book/test_fit_a_line.py — linear regression."""
    rng = np.random.RandomState(0)
    x = rng.rand(64, 13).astype("f4")
    y = (x @ rng.rand(13, 1)).astype("f4")

    def build():
        xd = fluid.data("x", [None, 13], "float32")
        yd = fluid.data("y", [None, 1], "float32")
        pred = layers.fc(xd, size=1)
        return layers.mean(layers.square_error_cost(pred, yd))

    losses = _run_static(build, {"x": x, "y": y}, lr=0.05)
    assert losses[-1] < losses[0] * 0.5


def test_recognize_digits_conv():
    """reference book/test_recognize_digits.py — LeNet-ish conv net."""
    pt.seed(0)
    rng = np.random.RandomState(0)
    img = rng.rand(16, 1, 28, 28).astype("f4")
    lab = rng.randint(0, 10, (16, 1)).astype("i8")

    def build():
        x = fluid.data("img", [None, 1, 28, 28], "float32")
        y = fluid.data("label", [None, 1], "int64")
        c1 = layers.conv2d(x, num_filters=6, filter_size=5, act="relu")
        p1 = layers.pool2d(c1, pool_size=2, pool_stride=2)
        c2 = layers.conv2d(p1, num_filters=16, filter_size=5, act="relu")
        p2 = layers.pool2d(c2, pool_size=2, pool_stride=2)
        pred = layers.fc(p2, size=10, act="softmax")
        return layers.mean(layers.cross_entropy(pred, y))

    losses = _run_static(build, {"img": img, "label": lab}, steps=15,
                         lr=0.1)
    assert losses[-1] < losses[0]


def test_word2vec():
    """reference book/test_word2vec.py — n-gram LM over embeddings."""
    pt.seed(0)
    rng = np.random.RandomState(1)
    V, E = 50, 16
    ctx = rng.randint(0, V, (32, 4)).astype("i8")
    nxt = rng.randint(0, V, (32, 1)).astype("i8")

    def build():
        words = fluid.data("ctx", [None, 4], "int64")
        label = fluid.data("next", [None, 1], "int64")
        emb = layers.embedding(words, size=[V, E])
        flat = layers.reshape(emb, (-1, 4 * E))
        h = layers.fc(flat, size=32, act="relu")
        pred = layers.fc(h, size=V, act="softmax")
        return layers.mean(layers.cross_entropy(pred, label))

    losses = _run_static(build, {"ctx": ctx, "next": nxt}, steps=25,
                         lr=0.2)
    assert losses[-1] < losses[0] * 0.8


def test_recommender_system():
    """reference book/test_recommender_system.py — two-tower embedding
    model with cosine similarity."""
    pt.seed(0)
    rng = np.random.RandomState(2)
    usr = rng.randint(0, 30, (32, 1)).astype("i8")
    mov = rng.randint(0, 40, (32, 1)).astype("i8")
    score = rng.rand(32, 1).astype("f4") * 5

    def build():
        u = fluid.data("usr", [None, 1], "int64")
        m = fluid.data("mov", [None, 1], "int64")
        y = fluid.data("score", [None, 1], "float32")
        ue = layers.fc(layers.reshape(
            layers.embedding(u, size=[30, 16]), (-1, 16)), size=16)
        me = layers.fc(layers.reshape(
            layers.embedding(m, size=[40, 16]), (-1, 16)), size=16)
        sim = layers.cos_sim(ue, me)
        pred = layers.scale(sim, scale=5.0)
        return layers.mean(layers.square_error_cost(pred, y))

    losses = _run_static(build, {"usr": usr, "mov": mov, "score": score},
                         steps=30, lr=0.3)
    assert losses[-1] < losses[0]


def test_understand_sentiment_conv():
    """reference book/notest_understand_sentiment.py — sequence conv net
    on padded text."""
    pt.seed(0)
    rng = np.random.RandomState(3)
    V, T = 60, 12
    sent = rng.randint(0, V, (16, T)).astype("i8")
    lab = rng.randint(0, 2, (16, 1)).astype("i8")

    def build():
        s = fluid.data("sent", [None, T], "int64")
        y = fluid.data("lab", [None, 1], "int64")
        emb = layers.embedding(s, size=[V, 16])
        conv = layers.sequence_conv(emb, num_filters=8, filter_size=3,
                                    act="relu")
        pooled = layers.sequence_pool(conv, "max")
        pred = layers.fc(pooled, size=2, act="softmax")
        return layers.mean(layers.cross_entropy(pred, y))

    losses = _run_static(build, {"sent": sent, "lab": lab}, steps=20,
                         lr=0.2)
    assert losses[-1] < losses[0]


def test_label_semantic_roles_crf():
    """reference book/test_label_semantic_roles.py — BiLSTM + linear
    chain CRF (dygraph form: the static CRF path is the same op)."""
    pt.seed(0)
    rng = np.random.RandomState(4)
    B, T, V, NT = 4, 6, 40, 5
    words = rng.randint(0, V, (B, T)).astype("i4")
    tags = rng.randint(0, NT, (B, T)).astype("i4")
    lens = np.asarray([6, 5, 6, 4], "i4")

    from paddle_tpu import nn, optimizer as opt
    from paddle_tpu.ops.crf import linear_chain_crf, crf_decoding

    emb = nn.Embedding(V, 16)
    lstm = nn.LSTM(16, 8, direction="bidirect")
    proj = nn.Linear(16, NT)
    trans = pt.Parameter(np.zeros((NT + 2, NT), "f4"))
    params = (list(emb.parameters()) + list(lstm.parameters()) +
              list(proj.parameters()) + [trans])
    o = opt.Adam(learning_rate=0.05, parameters=params)

    def step():
        e = emb(pt.to_tensor(words))
        h, _ = lstm(e)
        logits = proj(h)
        nll = linear_chain_crf(logits, pt.to_tensor(tags), trans,
                               pt.to_tensor(lens))
        loss = nll.mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return float(loss.numpy())

    losses = [step() for _ in range(12)]
    assert losses[-1] < losses[0]
    # decode runs and respects lengths
    e = emb(pt.to_tensor(words))
    h, _ = lstm(e)
    path = crf_decoding(proj(h), trans, length=pt.to_tensor(lens))
    assert path.shape == [B, T]


def test_rnn_encoder_decoder():
    """reference book/test_rnn_encoder_decoder.py — GRU encoder-decoder
    trained teacher-forced (padded redesign)."""
    pt.seed(0)
    rng = np.random.RandomState(5)
    V, T, B = 40, 7, 8
    src = rng.randint(1, V, (B, T)).astype("i8")
    tgt = rng.randint(1, V, (B, T)).astype("i8")

    def build():
        s = fluid.data("src", [None, T], "int64")
        t = fluid.data("tgt", [None, T], "int64")
        semb = layers.embedding(s, size=[V, 16])
        enc = layers.dynamic_gru(layers.fc(semb, size=3 * 16,
                                           num_flatten_dims=2), size=16)
        ctx = layers.sequence_last_step(enc)
        temb = layers.embedding(t, size=[V, 16])
        dec_in = layers.concat(
            [temb, layers.expand(layers.unsqueeze(ctx, [1]), [1, T, 1])],
            axis=-1)
        dec = layers.dynamic_gru(layers.fc(dec_in, size=3 * 16,
                                           num_flatten_dims=2), size=16)
        pred = layers.fc(dec, size=V, num_flatten_dims=2, act="softmax")
        # shift-by-one LM loss on the target
        return layers.mean(layers.cross_entropy(pred, layers.unsqueeze(
            t, [2])))

    losses = _run_static(build, {"src": src, "tgt": tgt}, steps=20,
                         lr=0.5)
    assert losses[-1] < losses[0] * 0.9


def test_machine_translation_beam_decode():
    """reference book/test_machine_translation.py — train briefly, then
    beam-search decode with the Transformer zoo model (the modern path the
    rebuild ships for MT)."""
    pt.seed(0)
    from paddle_tpu.models.transformer import Transformer
    from paddle_tpu import optimizer as opt
    rng = np.random.RandomState(6)
    V, B, T = 32, 4, 6
    model = Transformer(src_vocab_size=V, tgt_vocab_size=V, d_model=16,
                        num_heads=2, d_ff=32, num_encoder_layers=1,
                        num_decoder_layers=1, max_length=32)
    o = opt.Adam(learning_rate=0.01, parameters=model.parameters())
    src = pt.to_tensor(rng.randint(2, V, (B, T)).astype("i8"))
    tgt = pt.to_tensor(rng.randint(2, V, (B, T)).astype("i8"))

    def step():
        logits = model(src, tgt)
        loss = model.loss(logits, tgt)
        loss.backward()
        o.step()
        o.clear_grad()
        return float(loss.numpy())

    losses = [step() for _ in range(6)]
    assert losses[-1] < losses[0]
    out = model.generate(src, beam_size=2, max_len=8, bos_id=0, eos_id=1)
    ids = out[0] if isinstance(out, (list, tuple)) else out
    assert ids.shape[0] == B
