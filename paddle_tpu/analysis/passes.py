"""The built-in analysis passes.

Each is a function ``(ctx: AnalysisContext) -> list[Finding]`` registered
under its pass id (≙ REGISTER_PASS in the reference's
paddle/fluid/framework/ir). A pass that needs a context facility the
driver could not produce (no jaxpr because tracing failed, no grad info)
returns [] — the other passes still run.

Severity policy (what "clean bill" means for the zoo train steps):

* **error** — the program is wrong or will corrupt state: host
  concretization inside a traced fn, a donated buffer with no matching
  output (the caller's rebind target does not exist — every later read
  hits "Array has been deleted"), a trainable parameter with a
  structurally-zero gradient (the optimizer still applies weight decay /
  moment updates to it — the PR-2 frozen-param bug class).
* **warning** — probably costing performance or correctness headroom:
  host callbacks in the hot loop, f64 leaks, repeated shape/dtype-caused
  retraces, a flapping frozen set.
* **info** — worth knowing, expected in some designs: bf16→f32 upcasts
  inside an autocast region, low-count retrace summaries.
"""
from __future__ import annotations

from typing import List

import numpy as np

from .core import (AnalysisContext, Finding, eqn_source, is_structural_zero,
                   iter_eqns, register_pass)

__all__ = ["host_sync_pass", "donation_safety_pass", "dead_grad_pass",
           "dtype_hygiene_pass", "recompile_churn_pass",
           "collective_pairing_pass", "static_memory_pass",
           "donation_miss_pass", "sharding_consistency_pass"]


# ---------------------------------------------------------------------------
# 1. host-sync
# ---------------------------------------------------------------------------

_CALLBACK_PRIMS = {
    "pure_callback": "jax.pure_callback",
    "io_callback": "jax.experimental.io_callback",
    "debug_callback": "jax.debug.callback",
    "callback": "host callback",
}


@register_pass("host-sync")
def host_sync_pass(ctx: AnalysisContext) -> List[Finding]:
    """Host round-trips inside the traced computation.

    Two shapes: (a) the trace itself died on a concretization —
    ``.numpy()`` / ``float()`` / ``bool()`` / ``np.asarray`` on a traced
    value — which the driver caught and source-located (the raw
    ConcretizationTypeError fires deep inside jax where the call site is
    invisible); (b) callback-shaped eqns (pure_callback / io_callback),
    which run but serialize device against host every step."""
    out: List[Finding] = []
    if ctx.trace_error is not None:
        kind = type(ctx.trace_error).__name__
        out.append(Finding(
            pass_id="host-sync", severity="error",
            message=(f"host concretization inside the traced function "
                     f"({kind}): a .numpy()/float()/bool()/np.asarray on "
                     f"a traced value forces a device sync and breaks "
                     f"under jit"),
            source=ctx.trace_error_source,
            fix_hint=("keep host reads out of the step: return the value "
                      "and fetch it outside, or use a windowed flush "
                      "(Model.fit syncs once per log_freq steps)")))
        return out
    if ctx.closed_jaxpr is None:
        return out
    for eqn in iter_eqns(ctx.closed_jaxpr):
        prim = eqn.primitive.name
        if prim in _CALLBACK_PRIMS:
            out.append(Finding(
                pass_id="host-sync", severity="warning",
                message=(f"{_CALLBACK_PRIMS[prim]} inside the traced "
                         f"computation: one device->host->device round "
                         f"trip per execution"),
                source=eqn_source(eqn), primitive=prim,
                fix_hint=("intended for host-only kernels (e.g. "
                          "nonsymmetric eig, MIGRATION.md); keep it out "
                          "of per-step hot loops or precompute on host")))
    return out


# ---------------------------------------------------------------------------
# 2. donation-safety
# ---------------------------------------------------------------------------

def _aval_key(v):
    aval = v.aval
    return (tuple(aval.shape), str(aval.dtype))


@register_pass("donation-safety")
def donation_safety_pass(ctx: AnalysisContext) -> List[Finding]:
    """Donated inputs whose buffers are structurally unsafe.

    A donated input's buffer is deleted at dispatch; the caller's only
    valid move is rebinding to a same-shape/dtype output (the PR-2
    donated train step contract). Structurally checkable: (a) a donated
    invar with NO matching output aval — the rebind target does not
    exist, so the state the caller holds after the call is a deleted
    handle (error); (b) one donated invar feeding MORE outputs than
    exist buffers to alias (double-alias, error). The old boolean
    dead-donation warning moved to the byte-aware ``donation-miss``
    pass (ISSUE 18), which prices every donation decision."""
    out: List[Finding] = []
    closed, mask = ctx.closed_jaxpr, ctx.donated_invars
    if closed is None or not mask or not any(mask):
        return out
    jaxpr = closed.jaxpr
    donated = [v for v, d in zip(jaxpr.invars, mask) if d]

    # multiset of output avals available for aliasing
    from collections import Counter
    out_avals = Counter(_aval_key(v) for v in jaxpr.outvars
                        if not hasattr(v, "val"))
    outvar_counts = Counter(id(v) for v in jaxpr.outvars)

    for i, v in enumerate(donated):
        key = _aval_key(v)
        if outvar_counts.get(id(v), 0) > 1:
            out.append(Finding(
                pass_id="donation-safety", severity="error",
                message=(f"donated input #{i} ({key[1]}{list(key[0])}) is "
                         f"returned as more than one output — two "
                         f"outputs cannot alias one donated buffer"),
                fix_hint="return a copy for one of the aliases"))
            continue
        if out_avals.get(key, 0) > 0:
            out_avals[key] -= 1
            continue
        out.append(Finding(
            pass_id="donation-safety", severity="error",
            message=(f"donated input #{i} ({key[1]}{list(key[0])}) has no "
                     f"matching output: its buffer is deleted at "
                     f"dispatch but nothing replaces it — any state the "
                     f"caller rebinds is a deleted handle"),
            fix_hint=("return the updated value for every donated arg "
                      "(params/opt_state/buffers in a train step) or "
                      "drop it from donate_argnums")))
    return out


# ---------------------------------------------------------------------------
# 3. dead/frozen-grad
# ---------------------------------------------------------------------------

@register_pass("dead-grad")
def dead_grad_pass(ctx: AnalysisContext) -> List[Finding]:
    """Parameters whose cotangent is structurally zero in the grad jaxpr.

    jax AD materializes a symbolic-zero cotangent as
    ``broadcast_in_dim [0.0]`` — no dependence on any input. A trainable
    parameter with such a gradient is the exact latent bug PR 2 found by
    hand: the optimizer still applies weight decay and moment updates to
    it, silently training (decaying) a parameter the loss never sees.
    Requires grad info from the driver (``analyze_model`` supplies it);
    returns [] otherwise."""
    out: List[Finding] = []
    info = ctx.grad
    if not info or info.get("jaxpr") is None:
        return out
    closed = info["jaxpr"]
    names = info.get("names") or []
    trainable = info.get("trainable")
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    for i, v in enumerate(jaxpr.outvars):
        if not (hasattr(v, "val") or is_structural_zero(jaxpr, v)):
            continue
        if hasattr(v, "val") and np.any(np.asarray(v.val)):
            continue  # constant but nonzero: not a dead grad
        pname = names[i] if i < len(names) else f"output[{i}]"
        in_train = trainable is None or pname in trainable
        out.append(Finding(
            pass_id="dead-grad",
            severity="error" if in_train else "info",
            message=(f"parameter '{pname}' receives a structurally-zero "
                     f"gradient" +
                     (" but is in the trainable set — the optimizer "
                      "will still weight-decay/update it" if in_train
                      else " (frozen, as declared)")),
            fix_hint=("if freezing is intended, set stop_gradient=True "
                      "so the step bakes it out of the trainable split; "
                      "if not, the loss never reads this parameter — "
                      "check the forward wiring")))
    return out


# ---------------------------------------------------------------------------
# 4. dtype-hygiene
# ---------------------------------------------------------------------------

_MAX_SITES = 3  # provenance examples per finding class before aggregating


@register_pass("dtype-hygiene")
def dtype_hygiene_pass(ctx: AnalysisContext) -> List[Finding]:
    """f64 leaks and silent bf16->f32 upcasts.

    f64: TPUs emulate double precision at a large slowdown, and with
    jax's default x64-off config a float64 numpy input is silently
    downcast — both directions are a data-pipeline leak
    (``np.random.randn`` is float64!). bf16 upcasts: inside a program
    that demonstrably runs a bf16 region (bf16 inputs or f32->bf16
    downcasts present), every bf16->f32 convert re-doubles the memory
    the autocast saved — expected for loss accumulation, a bug when it
    hits activations."""
    out: List[Finding] = []
    for a in _np_leaves(ctx.args):
        if a.dtype in (np.float64, np.complex128):
            out.append(Finding(
                pass_id="dtype-hygiene", severity="warning",
                message=(f"float64 host input (shape "
                         f"{list(a.shape)}): silently downcast to f32 "
                         f"under jax's default config, or computed at "
                         f"~10x cost on TPU with x64 on"),
                fix_hint="cast the pipeline to float32 at the source "
                         "(np.float32 / .astype('float32'))"))
            break  # one finding per run is enough signal
    closed = ctx.closed_jaxpr
    if closed is None:
        return out

    def _dt(v) -> str:
        aval = getattr(v, "aval", None)
        return str(getattr(aval, "dtype", ""))

    f64_sites, upcast_sites = [], []
    has_bf16_region = any(_dt(v) == "bfloat16"
                          for v in closed.jaxpr.invars)
    for eqn in iter_eqns(closed):
        for v in eqn.outvars:
            if _dt(v) in ("float64", "complex128"):
                f64_sites.append(eqn_source(eqn))
                break
        if eqn.primitive.name == "convert_element_type":
            src_dt = _dt(eqn.invars[0])
            dst_dt = str(eqn.params.get("new_dtype", ""))
            if src_dt == "float32" and dst_dt == "bfloat16":
                has_bf16_region = True
            if src_dt == "bfloat16" and dst_dt == "float32":
                upcast_sites.append(eqn_source(eqn))
    if f64_sites:
        sites = ", ".join(s for s in f64_sites[:_MAX_SITES] if s)
        out.append(Finding(
            pass_id="dtype-hygiene", severity="warning",
            message=(f"{len(f64_sites)} eqn(s) produce float64/"
                     f"complex128 values (first at: {sites or 'n/a'})"),
            source=f64_sites[0],
            fix_hint="stay fp32/bf16 on TPU; fp64 is emulated"))
    if upcast_sites and has_bf16_region:
        sites = ", ".join(s for s in upcast_sites[:_MAX_SITES] if s)
        out.append(Finding(
            pass_id="dtype-hygiene", severity="info",
            message=(f"{len(upcast_sites)} bf16->f32 upcast(s) inside a "
                     f"bf16/autocast region (first at: {sites or 'n/a'})"),
            source=upcast_sites[0],
            fix_hint=("expected for loss/reduction accumulation; if an "
                      "activation path upcasts, check the amp "
                      "allow/deny lists")))
    return out


def _np_leaves(args):
    import jax
    for leaf in jax.tree_util.tree_leaves(
            args, is_leaf=lambda x: isinstance(x, np.ndarray)):
        if isinstance(leaf, np.ndarray):
            yield leaf


# ---------------------------------------------------------------------------
# 5. collective-pairing
# ---------------------------------------------------------------------------

def _axis_key(v):
    return tuple(v) if isinstance(v, (tuple, list)) else (v,)


@register_pass("collective-pairing")
def collective_pairing_pass(ctx: AnalysisContext) -> List[Finding]:
    """Reduce-scatter / all-gather pairing over the traced program.

    The ZeRO-sharded weight update's contract is a closed loop:
    gradients reduce-scatter over a mesh axis into 1/dp stripes, and
    the updated stripes all-gather back over the SAME axis and
    dimension with the SAME tiling. A reduce-scatter whose (axis,
    dimension, tiled) triple has no matching all-gather leaves the
    caller holding a shard it will treat as the full value — the
    sharded analog of a donated invar with no rebind target — and a
    gather on a DIFFERENT axis/dimension re-assembles the stripes in
    the wrong order (silently permuted parameters). psum-only programs
    (plain data-parallel grad sync) never trip this: the pass only
    speaks when reduce_scatter eqns exist."""
    out: List[Finding] = []
    if ctx.closed_jaxpr is None:
        return out
    # program order matters: an all-gather can only CLOSE a
    # reduce-scatter that precedes it (iter_eqns yields eqns in
    # program order) — an unrelated gather at the top of the step must
    # not be consumed as the match for a later unclosed scatter
    rs, ag = [], []
    for pos, eqn in enumerate(iter_eqns(ctx.closed_jaxpr)):
        name = eqn.primitive.name
        if name == "reduce_scatter":
            rs.append((pos, eqn))
        elif name == "all_gather":
            ag.append((pos, eqn))
    if not rs:
        return out

    def _ag_key(e):
        return (_axis_key(e.params.get("axis_name")),
                int(e.params.get("all_gather_dimension", 0)),
                bool(e.params.get("tiled", False)))

    unconsumed = list(ag)  # (pos, eqn), program order
    for rs_pos, e in rs:
        key = (_axis_key(e.params.get("axis_name")),
               int(e.params.get("scatter_dimension", 0)),
               bool(e.params.get("tiled", False)))
        match = next((i for i, (p, g) in enumerate(unconsumed)
                      if p > rs_pos and _ag_key(g) == key), None)
        if match is not None:
            unconsumed.pop(match)
            continue
        axis, dim, tiled = key
        same_axis = [
            _ag_key(g) for p, g in unconsumed
            if p > rs_pos and _ag_key(g)[0] == axis]
        if same_axis:
            have = ", ".join(f"dim={k[1]} tiled={k[2]}"
                             for k in same_axis)
            msg = (f"reduce-scatter over axis {axis} (dim={dim}, "
                   f"tiled={tiled}) does not match its closing "
                   f"all-gather ({have}): the stripes re-assemble "
                   f"permuted")
        else:
            msg = (f"reduce-scatter over axis {axis} (dim={dim}, "
                   f"tiled={tiled}) has no closing all-gather on that "
                   f"axis: downstream code holds a 1/N shard where it "
                   f"expects the full value")
        out.append(Finding(
            pass_id="collective-pairing", severity="error",
            message=msg, source=eqn_source(e),
            primitive="reduce_scatter",
            fix_hint=("close the sharded region with all_gather_in_axis "
                      "over the same axis/dimension/tiling, or keep the "
                      "value sharded on purpose via an explicit "
                      "out_spec (then psum_scatter is not the right "
                      "primitive to lint — wrap it outside the "
                      "analyzed step)")))
    return out


# ---------------------------------------------------------------------------
# 6. recompile-churn
# ---------------------------------------------------------------------------

# thresholds. Op-level sites ("op/<name>") legitimately trace once per
# distinct layer shape class while a deep network builds — breadth, not
# churn — so they stay info until the count looks like a data-driven
# shape explosion. Step-level sites (the hapi donated train step, user
# jits) have ONE expected signature per dataset: any repeated
# shape/dtype retrace there is the bucket-your-data bug.
_OP_SHAPE_INFO = 8
_OP_SHAPE_WARN = 32
_STEP_CHURN = 2
_FROZEN_CHURN = 2

# per-cause counts already reported by earlier analyze() runs in this
# process: each run reports only the DELTA since the previous one, so a
# report on target X never re-attributes another model's history (a
# long-lived notebook would otherwise see every old model's churn in
# every new report). A count that went DOWN means trace_probe.reset()
# ran — treat the site as fresh.
_reported: dict = {}


def _delta_sites(sites: dict) -> dict:
    out = {}
    for name, rec in sites.items():
        causes = rec.get("causes", {})
        seen = _reported.get(name, {})
        delta = {}
        for c, n in causes.items():
            prev = seen.get(c, 0)
            d = n - prev if n >= prev else n
            if d > 0:
                delta[c] = d
        if delta:
            out[name] = {"traces": rec.get("traces", 0), "causes": delta}
        _reported[name] = dict(causes)
    return out


@register_pass("recompile-churn")
def recompile_churn_pass(ctx: AnalysisContext) -> List[Finding]:
    """Why retraces fired, from the trace_probe site registry
    (framework/trace_probe.py) — every eager-op jit wrapper and the hapi
    donated train step record the signature they were traced with, and a
    re-trace is classified shape / dtype / static_arg / frozen_set /
    structure at trace time. This pass turns per-site counts into
    findings — scoped to retraces SINCE THE LAST analyze() run in this
    process; the raw cumulative ``dispatch/retrace_cause`` counters stay
    visible in monitor/Prometheus either way."""
    out: List[Finding] = []
    sites = _delta_sites(ctx.retrace_sites or {})
    total = 0
    cause_totals: dict = {}
    for name, rec in sites.items():
        causes = rec.get("causes", {})
        for c, n in causes.items():
            cause_totals[c] = cause_totals.get(c, 0) + n
            total += n
        is_op_site = name.startswith("op/")
        shape_n = causes.get("shape", 0)
        if is_op_site and shape_n >= _OP_SHAPE_INFO:
            out.append(Finding(
                pass_id="recompile-churn",
                severity="warning" if shape_n >= _OP_SHAPE_WARN
                else "info",
                message=(f"{name} re-traced {shape_n}x on new shape "
                         f"classes since the last analysis — each is a "
                         f"fresh XLA compile (expected once per layer "
                         f"shape; a count that keeps growing across "
                         f"steps is data-driven churn)"),
                fix_hint=("bucket variable-length inputs "
                          "(io.BucketedBatchSampler) or pad to a fixed "
                          "shape set; the persistent compile cache only "
                          "amortizes across runs, not shapes")))
        if not is_op_site and shape_n >= _STEP_CHURN:
            out.append(Finding(
                pass_id="recompile-churn", severity="warning",
                message=(f"{name} re-traced {shape_n}x on batch shape "
                         f"changes — the whole step recompiles each "
                         f"time"),
                fix_hint=("bucket variable-length inputs "
                          "(io.BucketedBatchSampler), pad, or pin "
                          "batch_size with drop_last=True")))
        if not is_op_site and causes.get("dtype", 0) >= _STEP_CHURN:
            out.append(Finding(
                pass_id="recompile-churn", severity="warning",
                message=(f"{name} re-traced {causes['dtype']}x on dtype "
                         f"changes (e.g. an f32 batch after bf16 "
                         f"warmup)"),
                fix_hint="pin the input dtype at the loader"))
        if causes.get("frozen_set", 0) >= _FROZEN_CHURN:
            out.append(Finding(
                pass_id="recompile-churn", severity="warning",
                message=(f"{name}: the frozen-parameter set changed "
                         f"{causes['frozen_set']}x — every flip re-traces "
                         f"the donated train step and reconciles "
                         f"optimizer slots"),
                fix_hint=("batch stop_gradient flips (progressive "
                          "unfreezing per phase, not per step)")))
    if total:
        detail = ", ".join(f"{c}={n}"
                           for c, n in sorted(cause_totals.items()))
        out.append(Finding(
            pass_id="recompile-churn", severity="info",
            message=(f"{total} retrace(s) across {len(sites)} trace "
                     f"site(s) since the last analysis: {detail}"),
            fix_hint=None))
    return out

# ---------------------------------------------------------------------------
# 7. static-memory (ISSUE 18)
# ---------------------------------------------------------------------------

@register_pass("static-memory")
def static_memory_pass(ctx: AnalysisContext) -> List[Finding]:
    """Donation-aware liveness scan (analysis/liveness.py): one info
    finding carrying ``static_peak_bytes`` and the fattest program
    point. Always info — the BUDGET verdict belongs to the callers
    (``GenerationEngine(hbm_budget_bytes=)``, ``--budget``), which hold
    the device context this pass does not."""
    if ctx.closed_jaxpr is None:
        return []
    from . import liveness
    rep = liveness.jaxpr_liveness(ctx.closed_jaxpr, ctx.donated_invars,
                                  top_k=3)
    pk = rep.peak
    return [Finding(
        pass_id="static-memory", severity="info",
        message=(f"static peak {rep.static_peak_bytes:,} B live "
                 f"(args {rep.arg_bytes:,} B, {rep.donated_bytes:,} B "
                 f"donated; fattest point: "
                 f"{pk.primitive if pk else 'n/a'} at "
                 f"{(pk.source if pk else None) or 'unknown source'})"),
        source=pk.source if pk else None,
        primitive=pk.primitive if pk else None,
        data=rep.as_dict())]


# ---------------------------------------------------------------------------
# 8. donation-miss (ISSUE 18; supersedes the boolean dead-donation check)
# ---------------------------------------------------------------------------

@register_pass("donation-miss")
def donation_miss_pass(ctx: AnalysisContext) -> List[Finding]:
    """Donation decisions priced in bytes.

    (a) A large invar (>= liveness.DONATION_MISS_MIN_BYTES) that dies
    before the program ends but is NOT donated: warning carrying the
    ``static_peak_bytes`` reduction donating it would buy — computed by
    an honest liveness re-scan, not a heuristic, so an invar whose
    lifetime spans the peak anyway is never flagged. (b) A donated
    invar the program never reads (the old donation-safety boolean
    dead-donation warning, now here with its bytes)."""
    if ctx.closed_jaxpr is None:
        return []
    from . import liveness
    out: List[Finding] = []
    for m in liveness.donation_misses(ctx.closed_jaxpr,
                                      ctx.donated_invars):
        if m["kind"] == "dead":
            out.append(Finding(
                pass_id="donation-miss", severity="warning",
                message=(f"donated input #{m['argnum']} "
                         f"({m['bytes']:,} B) is never read by the "
                         f"computation (dead donation)"),
                fix_hint="stop passing (and donating) the unused value",
                data=m))
        else:
            out.append(Finding(
                pass_id="donation-miss", severity="warning",
                message=(f"input #{m['argnum']} ({m['bytes']:,} B) dies "
                         f"before the program ends but is not donated — "
                         f"donating it would cut static peak memory by "
                         f"{m['saving_bytes']:,} B"),
                source=m["last_use_source"],
                fix_hint=(f"add argnum {m['argnum']} to donate_argnums "
                          f"(the caller must not reuse the buffer after "
                          f"the call)"),
                data=m))
    return out


# ---------------------------------------------------------------------------
# 9. sharding-consistency (ISSUE 18)
# ---------------------------------------------------------------------------

# an array entering a shard_map fully replicated below this size is a
# rounding error per device; above it, the per-device copy is worth a
# finding (embedding tables, block pools).
SHARDING_REPLICATED_MIN_BYTES = 1 << 20


def _mesh_axis_sizes(mesh) -> dict:
    try:
        return dict(mesh.shape)
    except Exception:
        try:
            return {a: int(s) for a, s in
                    zip(mesh.axis_names, mesh.devices.shape)}
        except Exception:
            return {}


def _collective_axes(eqn):
    name = eqn.primitive.name
    if name == "psum":
        return _axis_key(eqn.params.get("axes", ()))
    if name in ("reduce_scatter", "all_gather", "all_to_all",
                "ppermute", "pmax", "pmin"):
        return _axis_key(eqn.params.get("axis_name", ()))
    return None


@register_pass("sharding-consistency")
def sharding_consistency_pass(ctx: AnalysisContext) -> List[Finding]:
    """Static checks inside shard_map regions (the dp x mp composition
    bug class from "Automatic Cross-Replica Sharding"):

    * every collective's axis name must exist on the shard_map's mesh
      (error — an axis the mesh does not carry reduces over nothing);
    * a reduce_scatter inside the body must be closed by an all_gather
      over the SAME (axis, dimension, tiled) triple — the PR-10
      collective-pairing structure, scoped to the sharded region where
      the mesh context makes the message precise (error);
    * an array >= SHARDING_REPLICATED_MIN_BYTES entering the shard_map
      with a fully-replicated spec (no axis in its ``in_specs`` entry)
      costs its FULL bytes on EVERY device — warning with the per-device cost and the
      saving the largest mesh axis would buy."""
    if ctx.closed_jaxpr is None:
        return []
    from .liveness import aval_bytes
    out: List[Finding] = []
    for eqn in iter_eqns(ctx.closed_jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        mesh = eqn.params.get("mesh")
        axis_sizes = _mesh_axis_sizes(mesh)
        src = eqn_source(eqn)
        body = eqn.params.get("jaxpr")
        if body is None:
            continue
        if hasattr(body, "jaxpr"):
            body = body.jaxpr

        # (1) + (2): the body's collectives, in program order
        rs, ag = [], []
        for pos, e in enumerate(iter_eqns(body)):
            axes = _collective_axes(e)
            if axes is None:
                continue
            unknown = [a for a in axes
                       if isinstance(a, str) and a not in axis_sizes]
            if unknown:
                out.append(Finding(
                    pass_id="sharding-consistency", severity="error",
                    message=(f"{e.primitive.name} over axis "
                             f"{unknown[0]!r} inside shard_map, but the "
                             f"mesh only carries "
                             f"{sorted(axis_sizes) or 'no axes'}"),
                    source=eqn_source(e) or src,
                    primitive=e.primitive.name,
                    fix_hint="name a mesh axis (Mesh(..., axis_names=))"))
            if e.primitive.name == "reduce_scatter":
                rs.append((pos, e))
            elif e.primitive.name == "all_gather":
                ag.append((pos, e))

        def _ag_key(g):
            return (_axis_key(g.params.get("axis_name")),
                    int(g.params.get("all_gather_dimension", 0)),
                    bool(g.params.get("tiled", False)))

        unconsumed = list(ag)
        for rs_pos, e in rs:
            key = (_axis_key(e.params.get("axis_name")),
                   int(e.params.get("scatter_dimension", 0)),
                   bool(e.params.get("tiled", False)))
            match = next((i for i, (p, g) in enumerate(unconsumed)
                          if p > rs_pos and _ag_key(g) == key), None)
            if match is not None:
                unconsumed.pop(match)
                continue
            later = [_ag_key(g) for p, g in unconsumed if p > rs_pos]
            have = ", ".join(
                f"axis={k[0]} dim={k[1]} tiled={k[2]}" for k in later) \
                or "none"
            out.append(Finding(
                pass_id="sharding-consistency", severity="error",
                message=(f"reduce_scatter over axis {key[0]} (dim="
                         f"{key[1]}, tiled={key[2]}) inside shard_map "
                         f"on mesh {axis_sizes} is not closed by a "
                         f"matching all_gather (later gathers: {have}) "
                         f"— the PR-10 pairing contract, scoped to the "
                         f"sharded region"),
                source=eqn_source(e) or src,
                primitive="reduce_scatter",
                fix_hint=("all_gather over the same axis/dimension/"
                          "tiling before leaving the shard_map body")))

        # (3): large fully-replicated operands
        # a PartitionSpec an operand: an entry a dim, None where replicated
        in_specs = eqn.params.get("in_specs") or ()
        for k, (v, spec) in enumerate(zip(eqn.invars, in_specs)):
            if any(d is not None for d in spec):   # partitioned somewhere
                continue
            b = aval_bytes(getattr(v, "aval", None))
            if b < SHARDING_REPLICATED_MIN_BYTES:
                continue
            biggest = max(axis_sizes.values()) if axis_sizes else 1
            out.append(Finding(
                pass_id="sharding-consistency", severity="warning",
                message=(f"operand #{k} ({b:,} B) enters shard_map "
                         f"fully replicated: {b:,} B resident on EVERY "
                         f"device of mesh {axis_sizes} — sharding its "
                         f"largest dim over the biggest axis would cut "
                         f"the per-device cost to ~{b // biggest:,} B"),
                source=src, primitive="shard_map",
                fix_hint=("give the operand a PartitionSpec over a mesh "
                          "axis (in_specs=P('mp', ...)), or keep small/"
                          "genuinely-shared state replicated on "
                          "purpose"),
                data={"argnum": k, "bytes": b,
                      "per_device_sharded_bytes": b // biggest}))
    return out
