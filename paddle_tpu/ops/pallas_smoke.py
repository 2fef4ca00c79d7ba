"""TPU smoke gate for the Pallas kernel tier.

Interpret-mode parity tests (tests/test_pallas.py,
tests/test_ragged_attention.py) cannot catch Mosaic *lowering* errors — a
slice not aligned to the HBM tiling, a block that does not fit VMEM. This
gate executes every Pallas kernel of the main path non-interpreted on the
real backend, at the head width the supported model has (Dh = 64) and at
sizes that cross each kernel's block grid, and compares the result with
the kernel's plain reference, before the kernels serve a real model.

A kernel that fails its smoke is an ERROR naming the kernel, raised to
whoever asked (``Model.fit`` / ``ParallelEngine`` / ``GenerationEngine``).
Nothing here switches ``FLAGS_use_pallas`` off: that flag is the user's
explicit choice, and a run that silently trained on the lax compositions
would report numbers for a path nobody asked for.
"""
from __future__ import annotations

from typing import Callable, Dict

from ..framework.flags import flag_value

__all__ = ["PallasSmokeError", "run_smoke", "ensure"]

_state = {"passed": False}


class PallasSmokeError(RuntimeError):
    """A Pallas kernel failed to compile, run or match its reference on
    this chip."""


def _assert_close(name, got, want, rel):
    """max |got - want| within ``rel`` of the reference's largest
    magnitude. The bounds are wide enough for the MXU's bf16 passes on
    f32 operands: a wrong layout or mask is off by the magnitude itself,
    not by a few percent of it."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    tol = rel * float(np.max(np.abs(want))) + 1e-6
    if not err <= tol:          # also catches NaN
        raise FloatingPointError(
            f"{name}: max |kernel - reference| = {err} > {tol}")


def _grad_parity(name, kernel, reference, args, loss_rel, grad_rel):
    """``sum(f(*args) ** 2)`` and its gradients, kernel against the lax
    composition the kernel overrides (ops/nn_ops.py — the reference of
    the interpret-mode parity tests too)."""
    import jax
    import jax.numpy as jnp

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: (f(*a).astype(jnp.float32) ** 2).sum(),
            argnums=tuple(range(len(args)))))(*args)

    got, want = run(kernel), run(reference)
    _assert_close(f"{name} loss", got[0], want[0], loss_rel)
    for i, (g, w) in enumerate(zip(got[1], want[1])):
        _assert_close(f"{name} d(arg {i})", g, w, grad_rel)


def _smoke_flash_attention():
    import jax.numpy as jnp
    import numpy as np
    from .pallas_kernels import flash_attention
    from .registry import get_op

    rng = np.random.RandomState(0)
    qkv = [jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
           for _ in range(3)]
    lax_sdpa = get_op("scaled_dot_product_attention").fn
    _grad_parity("flash attention",
                 lambda q, k, v: flash_attention(q, k, v, is_causal=True),
                 lambda q, k, v: lax_sdpa(q, k, v, is_causal=True),
                 qkv, loss_rel=2e-2, grad_rel=5e-2)


def _smoke_fused_layer_norm():
    import jax.numpy as jnp
    import numpy as np
    from .pallas_kernels import fused_layer_norm
    from .registry import get_op

    rng = np.random.RandomState(0)
    args = [jnp.asarray(rng.randn(128, 768), jnp.float32),
            jnp.asarray(rng.randn(768), jnp.float32),
            jnp.asarray(rng.randn(768), jnp.float32)]
    _grad_parity("fused LayerNorm", fused_layer_norm,
                 get_op("layer_norm").fn, args, loss_rel=1e-3,
                 grad_rel=1e-2)


def _smoke_fused_adamw():
    """(1000, 768): 6000 rows of 128 lanes — twelve grid steps, the
    last one ragged."""
    import jax.numpy as jnp
    import numpy as np
    from .pallas_kernels import fused_adamw

    rng = np.random.RandomState(0)
    p = rng.randn(1000, 768).astype(np.float32)
    g = rng.randn(1000, 768).astype(np.float32)
    z = jnp.zeros(p.shape, jnp.float32)
    lr, b1, b2, eps, wd = 1e-3, 0.9, 0.999, 1e-8, 0.01
    new_p, new_m, new_v = fused_adamw(
        jnp.asarray(p), jnp.asarray(g), z, z, lr, b1, b2, eps, wd, 1)
    m = (1 - b1) * g
    v = (1 - b2) * g * g
    want = p - lr * ((m / (1 - b1)) / (np.sqrt(v / (1 - b2)) + eps)
                     + wd * p)
    _assert_close("AdamW p", new_p, want, 1e-5)
    _assert_close("AdamW m", new_m, m, 1e-5)
    _assert_close("AdamW v", new_v, v, 1e-5)


def _smoke_ragged_paged_attention():
    """Fused serving kernel at GPT-2's head width (Dh = 64, so the K|V
    block tile is exactly 128 lanes): a mixed decode + prefill-chunk
    ragged batch against the numpy oracle, for a float32 and a bfloat16
    pool at block 16 and an int8 pool at block 32 — the lowering gate for
    ``GenerationEngine``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from .ragged_paged_attention import (ragged_layout,
                                         ragged_paged_attention,
                                         reference_ragged_attention)

    H, DH, S, T = 3, 64, 2, 2
    for dtype, bs, tol in (("float32", 16, 2e-2), ("bfloat16", 16, 5e-2),
                           ("int8", 32, 5e-2)):
        rng = np.random.RandomState(0)
        quant = dtype == "int8"
        if quant:
            pool = rng.randint(-127, 128, (2, 6, H, bs, 2 * DH)).astype(
                np.int8)
            scales = (rng.rand(2, 2, 6, H) / 64).astype(np.float32)
        else:
            pool = np.asarray(jnp.asarray(
                rng.randn(2, 6, H, bs, 2 * DH), dtype).astype(jnp.float32))
            scales = None
        qdt = jnp.float32 if dtype == "float32" else jnp.bfloat16
        tables = np.zeros((S, T), np.int32)
        tables[0, :2] = [1, 3]
        tables[1, :1] = [4]
        # seq 0 decodes one row at position bs + 4 (two blocks); seq 1
        # is a 9-row prefill chunk from position 0
        q_lens, pos0s = [1, 9], [bs + 4, 0]
        blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, pos0s,
                                                    q_bucket=24)
        q = np.asarray(jnp.asarray(rng.randn(H, 24, DH), qdt)
                       .astype(jnp.float32))
        lo = np.zeros(S, np.int32)
        kv_len = np.asarray([bs + 5, 9], np.int32)
        out = jax.jit(lambda q_, p_, s_: ragged_paged_attention(
            q_, p_, 1, blk_seq, qstart, pos0, tables, lo, kv_len,
            scales=s_))(jnp.asarray(q, qdt), jnp.asarray(pool, dtype),
                        None if scales is None else jnp.asarray(scales))
        rows = [(s, i) for s in range(S) for i in range(q_lens[s])]
        ref = reference_ragged_attention(
            np.stack([q[:, qstart[s] + i] for s, i in rows]), pool, 1,
            [s for s, _ in rows], [pos0s[s] + i for s, i in rows],
            [list(t) for t in tables], lo, scales=scales)
        got = np.stack([np.asarray(out.astype(jnp.float32))[:,
                                                            qstart[s] + i]
                        for s, i in rows])
        _assert_close(f"ragged {dtype}", got, ref, tol)


_KERNEL_SMOKES: Dict[str, Callable[[], None]] = {
    "flash_attention": _smoke_flash_attention,
    "fused_layer_norm": _smoke_fused_layer_norm,
    "fused_adamw": _smoke_fused_adamw,
    "ragged_paged_attention": _smoke_ragged_paged_attention,
}


def run_smoke() -> None:
    """Execute every Pallas kernel on the current backend and compare it
    with its reference. Raises :class:`PallasSmokeError` naming the
    first kernel that fails; mutates no flag."""
    for name, fn in _KERNEL_SMOKES.items():
        try:
            fn()
        except Exception as e:
            raise PallasSmokeError(
                f"Pallas kernel {name!r} failed its smoke on this chip: "
                f"{type(e).__name__}: {e}") from e


def ensure() -> bool:
    """Gate: on a TPU, smoke all kernels once per process and raise
    :class:`PallasSmokeError` if one fails. Returns whether the Pallas
    tier is enabled (``FLAGS_use_pallas``, the user's choice — never
    changed here). Off-TPU the kernels only run interpreted (tests), and
    there is nothing to gate."""
    from .pallas_kernels import _on_tpu

    if not flag_value("FLAGS_use_pallas"):
        return False
    if _on_tpu() and not _state["passed"]:
        run_smoke()
        _state["passed"] = True
    return True
