"""ISSUE 18: the fit-before-compile HBM gate on GenerationEngine.

``GenerationEngine(hbm_budget_bytes=...)`` statically plans the LARGEST
decode-path bucket (donation-aware liveness + the pool/scales ledger)
at construction and raises :class:`PlanError` naming the fattest
program point BEFORE any compile — ``compile/count`` must not move. The
same :meth:`plan_replica` call is the elastic scale-out path's dry
admission check. On CPU the backend reports no device memory limit, so
the default gate stays inert (``_plan is None``) and every budget here
is explicit.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import monitor
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.serving import GenerationEngine, PlanError


@pytest.fixture()
def tiny_model():
    paddle.framework.random.seed(0)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    return m


def _compiles():
    return monitor.stat_get("compile/count") or 0


def test_over_budget_construction_raises_named_planerror(tiny_model):
    c0 = _compiles()
    with pytest.raises(PlanError) as ei:
        GenerationEngine(tiny_model, num_slots=4, max_len=64,
                         block_size=16,
                         hbm_budget_bytes=64 * 1024)
    assert _compiles() - c0 == 0          # fit BEFORE compile
    msg = str(ei.value)
    assert "does not fit" in msg and "fattest program point" in msg
    # names an actual primitive with its live bytes and source
    plan = ei.value.plan
    assert plan["fits"] is False
    assert plan["peak_point"]["primitive"]
    assert plan["peak_point"]["live_bytes"] > 64 * 1024
    assert plan["peak_point"]["primitive"] in msg
    assert plan["static_peak_bytes"] > plan["budget_bytes"] == 64 * 1024
    assert plan["headroom_bytes"] < 0


def test_generous_budget_constructs_with_fitting_plan(tiny_model):
    eng = GenerationEngine(tiny_model, num_slots=4, max_len=64,
                           block_size=16,
                           hbm_budget_bytes=1 << 33)
    try:
        plan = eng._plan
        assert plan is not None and plan["fits"] is True
        assert plan["headroom_bytes"] > 0
        assert plan["pool_bytes"] == eng._pool.capacity_bytes
        # the engine still serves normally after planning
        out = eng.submit(np.arange(1, 6, dtype=np.int32),
                         max_new_tokens=4).result(timeout=300)
        assert len(out) == 9
    finally:
        eng.close()


def test_cpu_default_budget_is_inert(tiny_model):
    """No explicit budget + a backend that reports no memory limit
    (CPU): the gate must stay inert, never invent a budget."""
    eng = GenerationEngine(tiny_model, num_slots=4, max_len=64,
                           block_size=16)
    try:
        assert eng._hbm_budget_bytes is None
        assert eng._plan is None
    finally:
        eng.close()


def test_plan_replica_is_a_dry_admission_check(tiny_model):
    """plan_replica() on a LIVE engine answers 'would another budget
    fit' without compiling or touching the serving state."""
    eng = GenerationEngine(tiny_model, num_slots=4, max_len=64,
                           block_size=16)
    try:
        c0 = _compiles()
        plan = eng.plan_replica(1 << 33)
        assert _compiles() - c0 == 0
        assert plan["fits"] is True and plan["flavor"] == "fused"
        assert plan["table_bucket"] == eng._pool.max_table_len
        assert plan["static_peak_bytes"] > plan["pool_bytes"] > 0
        assert plan["timeline"]                # top-k blame points
        with pytest.raises(PlanError):
            eng.plan_replica(64 * 1024)
        assert _compiles() - c0 == 0
    finally:
        eng.close()


def test_plan_covers_every_engine_flavor(tiny_model):
    """fused / spec flavors both plan at zero compiles, and the fused
    plan prices the largest (q, table) bucket."""
    from paddle_tpu.ops.ragged_paged_attention import BLOCK_Q

    flavors = [
        (dict(block_size=16), "fused"),
        (dict(block_size=16, spec_draft=tiny_model, spec_k=3), "spec"),
    ]
    for kwargs, flavor in flavors:
        eng = GenerationEngine(tiny_model, num_slots=4, max_len=64,
                               **kwargs)
        try:
            c0 = _compiles()
            plan = eng.plan_replica(1 << 33)
            assert _compiles() - c0 == 0, flavor
            assert plan["flavor"] == flavor
            assert plan["fits"] is True
            assert plan["static_peak_bytes"] > 0
            if flavor == "fused":
                assert plan["q_bucket"] >= 4 * BLOCK_Q  # all-slots bucket
        finally:
            eng.close()


def test_quantized_pool_ledger_in_plan(tiny_model):
    """int8 blocks: the plan's pool ledger must be the quantized
    capacity (blocks + scales), far below the fp32 figure."""
    eng_q = GenerationEngine(tiny_model, num_slots=4, max_len=64,
                             block_size=32, kv_dtype="int8")
    eng_f = GenerationEngine(tiny_model, num_slots=4, max_len=64,
                             block_size=32)
    try:
        pq = eng_q.plan_replica(1 << 33)
        pf = eng_f.plan_replica(1 << 33)
        assert pq["pool_bytes"] == eng_q._pool.capacity_bytes
        assert pq["pool_bytes"] < pf["pool_bytes"] / 2
        # (the step's own peak is NOT smaller at this toy size: the
        # quantized append requantizes every touched block in float32,
        # [rows, heads, block, lanes], which outweighs a 300 KB pool)
        assert pq["step_peak_bytes"] - pq["pool_bytes"] > 0
    finally:
        eng_q.close()
        eng_f.close()


def test_sharded_plan_bills_per_device_pool(tiny_model):
    """mesh= engines: the step's operand carries the GLOBAL pool shape,
    but the plan must bill the PER-DEVICE capacity (paging.py's ledger
    figure) — the mp=2 plan is cheaper than single-device."""
    import jax
    from jax.sharding import Mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    eng_s = GenerationEngine(tiny_model, num_slots=4, max_len=64,
                             block_size=16,
                             mesh=mesh)
    try:
        ps = eng_s.plan_replica(1 << 33)
        assert ps["pool_bytes"] == eng_s._pool.capacity_bytes
        assert ps["fits"] is True
    finally:
        eng_s.close()
