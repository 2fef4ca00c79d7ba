"""Forward+backward smoke for the vision-zoo families no other test
builds (reference: python/paddle/vision/models/*). Tiny inputs: the
point is constructor arguments, layer wiring, and gradient flow, not
accuracy."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.vision import models as M


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_caches():
    """This module compiles some of the suite's biggest CPU programs
    (inception 299px, alexnet 224px) and runs near the END of the
    alphabetical order, on top of ~1100 accumulated executables — the
    combination has segfaulted inside XLA's CPU compiler (resource
    exhaustion, not a logic bug: the module passes standalone). Dropping
    the accumulated jit caches first keeps it comfortably inside the
    process limits; later modules simply recompile on demand."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()

# (constructor name, kwargs, input hw) — 32px keeps pooling valid
CASES = [
    ("alexnet", {}, 224),            # big stem: needs full-size input
    ("vgg11", {}, 32),
    ("vgg16", {"batch_norm": True}, 32),
    ("inception_v3", {}, 75),        # the least its stem and two
                                     # reductions leave a pixel of
    ("mobilenet_v1", {}, 32),
    ("mobilenet_v2", {}, 32),
    ("squeezenet1_0", {}, 64),
    ("squeezenet1_1", {}, 64),
    ("wide_resnet50_2", {}, 32),
]


@pytest.mark.parametrize("name,kwargs,hw", CASES,
                         ids=[c[0] for c in CASES])
def test_zoo_forward_backward(name, kwargs, hw):
    paddle.seed(0)
    net = getattr(M, name)(num_classes=7, **kwargs)
    net.train()
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 3, hw, hw).astype("float32"))
    out = net(x)
    assert tuple(out.shape) == (2, 7), name
    loss = out.sum()
    loss.backward()
    # at least one conv weight received a finite gradient
    grads = [p.grad for p in net.parameters() if p.grad is not None]
    assert grads, f"{name}: no gradients flowed"
    assert all(np.isfinite(g.numpy()).all() for g in grads[:3])
