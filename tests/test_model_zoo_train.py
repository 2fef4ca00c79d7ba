"""The extended model zoo in train mode: an eager step a model (forward,
backward, SGD) and the heads a train-mode forward returns. One file beside
``test_vision_ops_models.py``, whose class this was, because a file is what
the suite's workers are handed (PR 45), and named to be handed out in
the middle of a run, not beside ``test_vision_zoo_forward.py`` at its end
(two files of two minutes each were the run's tail); the zoo's forward +
backward at every architecture is that file's."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


def t(x):
    return paddle.to_tensor(np.asarray(x))


class TestModelZooTrains:
    # 32 pixels is the least every stem here leaves a pixel of. An eager
    # step's cost on the CPU is its op count, not its pixels: every new
    # (op, shape) is a small compile, forward and backward. densenet121
    # has ~1,500 of them (a minute by itself): its train step is ``slow``,
    # outside tier-1, where ``test_densenet121_forward`` holds its wiring
    # and the three other cases the eager backward through the same layer
    # kinds (conv, batch norm, concat, pooling).
    @pytest.mark.parametrize("name", [
        "squeezenet1_1",
        pytest.param("densenet121", marks=pytest.mark.slow),
        "mobilenet_v3_small", "shufflenet_v2_x0_25"])
    def test_new_models_train_step(self, name):
        import paddle_tpu.vision.models as M
        rng = np.random.RandomState(7)
        model = getattr(M, name)(num_classes=4)
        model.train()
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())
        x = t(rng.randn(2, 3, 32, 32).astype(np.float32))
        y = t(rng.randint(0, 4, (2,)))
        out = model(x)
        loss = F.cross_entropy(out, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        assert np.isfinite(float(loss))

    def test_densenet121_forward(self):
        """The 121-layer table through four dense blocks and three
        transitions, in train mode (batch statistics): logits of the
        asked width, finite."""
        import paddle_tpu.vision.models as M
        model = M.densenet121(num_classes=4)
        model.train()
        x = t(np.random.RandomState(7).randn(2, 3, 32, 32)
              .astype(np.float32))
        out = model(x)
        assert tuple(out.shape) == (2, 4)
        assert np.isfinite(out.numpy()).all()

    def test_googlenet_aux_heads(self):
        import paddle_tpu.vision.models as M
        m = M.googlenet(num_classes=4)
        m.train()
        x = t(np.random.randn(1, 3, 96, 96).astype(np.float32))
        out, aux1, aux2 = m(x)
        assert tuple(out.shape) == (1, 4)
        assert tuple(aux1.shape) == (1, 4) and tuple(aux2.shape) == (1, 4)
        m.eval()
        out = m(x)
        assert tuple(out.shape) == (1, 4)
