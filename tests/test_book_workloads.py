"""Book-style end-to-end workloads (reference: the fluid book tests,
python/paddle/fluid/tests/book/): small canonical models must train to
a better-than-chance state with the stock toolchain — the reference's
acceptance style, ported to the TPU-native stack. fit_a_line already
lives in test_static_program; these cover sentiment (variable-length
biLSTM) and word2vec (CBOW embeddings)."""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def test_understand_sentiment_bilstm():
    """Synthetic sentiment: class = whether token 7 appears. A
    variable-length biLSTM + max-pool classifier must beat 90% on its
    training set within a few epochs. Twenty eager steps at a rate of
    2e-2 (it reads 100% from the fifteenth on, a loss of 0.02 at the
    twentieth; sixty at 5e-3 were two minutes of eager LSTM steps). The
    eager backward of an RNN under ``sequence_length`` is
    ``test_rnn_sequence_length.py``'s."""
    paddle.seed(0)
    rng = np.random.RandomState(0)
    V, T, N = 20, 12, 64
    xs = rng.randint(1, V, (N, T)).astype(np.int64)
    lens = rng.randint(4, T + 1, N)
    for i, n in enumerate(lens):
        xs[i, n:] = 0
    ys = np.array([(7 in xs[i, :lens[i]]) for i in range(N)], np.int64)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(V, 16)
            self.lstm = nn.LSTM(16, 16, direction="bidirect")
            self.fc = nn.Linear(32, 2)

        def forward(self, x, lengths):
            h, _ = self.lstm(self.emb(x), sequence_length=lengths)
            # padded steps are zeroed -> max over time is mask-safe
            return self.fc(h.max(axis=1))

    net = Net()
    opt = paddle.optimizer.Adam(learning_rate=2e-2,
                                parameters=net.parameters())
    x_t, l_t = paddle.to_tensor(xs), paddle.to_tensor(lens)
    y_t = paddle.to_tensor(ys)
    for _ in range(20):
        loss = F.cross_entropy(net(x_t, l_t), y_t)
        loss.backward()
        opt.step()
        opt.clear_grad()
    pred = net(x_t, l_t).numpy().argmax(-1)
    acc = (pred == ys).mean()
    assert acc > 0.9, f"sentiment accuracy {acc}"


def test_word2vec_cbow():
    """CBOW on a tiny corpus with a planted co-occurrence structure:
    after training, a word's nearest embedding neighbors come from its
    own topic cluster (reference book test's learned-embedding check)."""
    paddle.seed(1)
    rng = np.random.RandomState(1)
    # two topics of 5 words each; sentences stay within a topic
    V, D = 10, 8
    ctx, tgt = [], []
    for _ in range(400):
        topic = rng.randint(2)
        words = rng.choice(np.arange(5) + 5 * topic, size=4,
                           replace=True)
        ctx.append(words[:3])
        tgt.append(words[3])
    ctx = np.asarray(ctx, np.int64)
    tgt = np.asarray(tgt, np.int64)

    class CBOW(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(V, D)
            self.out = nn.Linear(D, V)

        def forward(self, c):
            return self.out(self.emb(c).mean(axis=1))

    net = CBOW()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    c_t, t_t = paddle.to_tensor(ctx), paddle.to_tensor(tgt)
    first = None
    for _ in range(80):
        loss = F.cross_entropy(net(c_t), t_t)
        loss.backward()
        opt.step()
        opt.clear_grad()
        first = first if first is not None else float(loss)
    assert float(loss) < first * 0.7
    # embedding geometry: nearest neighbor shares the topic
    W = net.emb.weight.numpy()
    Wn = W / (np.linalg.norm(W, axis=1, keepdims=True) + 1e-8)
    sims = Wn @ Wn.T
    np.fill_diagonal(sims, -np.inf)
    hits = sum((np.argmax(sims[w]) // 5) == (w // 5) for w in range(V))
    assert hits >= 8, f"only {hits}/10 words cluster by topic"


def test_summary_and_flops_report():
    m = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1), nn.ReLU(),
                      nn.Flatten(), nn.Linear(8 * 8 * 8, 10))
    info = paddle.summary(m, (1, 3, 8, 8))
    # conv 3*8*9+8 = 224; linear 512*10+10 = 5130
    assert info["total_params"] == 224 + 5130
    assert info["trainable_params"] == info["total_params"]
    assert paddle.flops(m, [1, 3, 8, 8]) > 0
