"""Program linter core: Findings, the pass registry, and the analyze()
driver.

Reference analog: the reference's IR-pass layer
(paddle/fluid/framework/ir — ``Pass::Apply`` over a ProgramDesc graph,
registered via ``REGISTER_PASS``) and the InferMeta pre-flight checks.
TPU-native stance: the IR *is* the jaxpr. ``analyze()`` closed-jaxpr-
traces a callable (or replays a captured static Program) WITHOUT
compiling or executing it, then runs a pipeline of registered passes
over the trace; each pass emits structured :class:`Finding`s carrying
severity, eqn provenance (file:line of the op that produced the value)
and a fix hint. The properties checked are exactly the ones that are
statically derivable from the traced program — the same argument that
makes redistribution cost readable from shardings (arXiv:2112.01075)
and weight-update structure readable from the grad graph
(arXiv:2004.13336).

Observability contract: every run bumps ``analysis/runs`` and
``analysis/findings`` (+ per-severity and per-pass counters) and records
an ``analysis/pass_ms/<pass>`` histogram in framework/monitor.py, so the
linter's own cost and yield are visible in ``bench.py --dry-run`` and
the Prometheus exposition like any other subsystem.
"""
from __future__ import annotations

import time
import traceback as _tb
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import numpy as np

from ..framework.monitor import stat_add, stat_observe
from ..profiler import span as _prof

__all__ = ["Finding", "Report", "AnalysisError", "register_pass",
           "all_passes", "analyze", "AnalysisContext", "iter_eqns",
           "eqn_source", "is_structural_zero", "SEVERITIES"]

# ordered weakest-first; rank index is the comparison key
SEVERITIES = ("info", "warning", "error")


@dataclass
class Finding:
    """One diagnosed program property (≙ a pass's graph-viz annotation in
    the reference IR layer, made machine-readable)."""

    pass_id: str
    severity: str               # "info" | "warning" | "error"
    message: str
    source: Optional[str] = None      # "file:line (fn)" eqn provenance
    primitive: Optional[str] = None   # offending jaxpr primitive, if any
    fix_hint: Optional[str] = None
    data: Optional[dict] = None       # machine-readable payload (bytes
                                      # figures etc.) for --json consumers

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"bad severity {self.severity!r}")

    def rank(self) -> int:
        return SEVERITIES.index(self.severity)


class AnalysisError(RuntimeError):
    """Raised by error-mode integrations (``Model.fit(analyze='error')``)
    when a run produces error-severity findings. Carries the report."""

    def __init__(self, report: "Report"):
        self.report = report
        errs = report.errors()
        super().__init__(
            f"static analysis found {len(errs)} error-severity "
            f"finding(s) in {report.target}:\n{report.table()}")


@dataclass
class Report:
    """All findings of one analyze() run, renderable as a table."""

    target: str
    findings: List[Finding] = field(default_factory=list)
    n_eqns: int = 0
    passes_run: List[str] = field(default_factory=list)

    def by_severity(self, severity: str) -> List[Finding]:
        return [f for f in self.findings if f.severity == severity]

    def errors(self) -> List[Finding]:
        return self.by_severity("error")

    def warnings(self) -> List[Finding]:
        return self.by_severity("warning")

    def ok(self) -> bool:
        """True when no error-severity findings (the pre-flight gate)."""
        return not self.errors()

    def table(self) -> str:
        """Human-readable findings table (worst first)."""
        if not self.findings:
            return (f"analysis of {self.target}: clean "
                    f"({self.n_eqns} eqns, "
                    f"passes: {', '.join(self.passes_run) or 'none'})")
        ordered = sorted(self.findings, key=lambda f: -f.rank())
        rows = [(f.severity.upper(), f.pass_id, f.source or "-",
                 f.message) for f in ordered]
        widths = [max(len(r[i]) for r in rows) for i in range(3)]
        lines = [f"analysis of {self.target}: "
                 f"{len(self.errors())} error(s), "
                 f"{len(self.warnings())} warning(s), "
                 f"{len(self.by_severity('info'))} info"]
        for (sev, pid, src, msg), f in zip(rows, ordered):
            lines.append(f"  {sev:<{widths[0]}}  {pid:<{widths[1]}}  "
                         f"{src:<{widths[2]}}  {msg}")
            if f.fix_hint:
                pad = " " * (6 + widths[0])
                lines.append(f"{pad}hint: {f.fix_hint}")
        return "\n".join(lines)

    def __repr__(self):
        return (f"<Report {self.target}: {len(self.findings)} findings "
                f"({len(self.errors())} errors)>")


# ---------------------------------------------------------------------------
# pass registry (≙ REGISTER_PASS in paddle/fluid/framework/ir/pass.h)
# ---------------------------------------------------------------------------

_PASSES: Dict[str, Callable] = {}


def register_pass(pass_id: str):
    """Register ``fn(ctx) -> iterable[Finding]`` under ``pass_id``.
    Passes run in registration order; a pass that needs a facility the
    context lacks (no jaxpr, no grad info) must return [] rather than
    raise."""

    def deco(fn):
        _PASSES[pass_id] = fn
        return fn

    return deco


def all_passes() -> List[str]:
    return list(_PASSES)


@dataclass
class AnalysisContext:
    """Everything a pass may inspect. Fields are None when the driver
    could not (or was not asked to) produce them."""

    target_name: str
    closed_jaxpr: Any = None          # jax ClosedJaxpr of the target
    trace_error: Any = None           # concretization exc caught in trace
    trace_error_source: Optional[str] = None
    args: tuple = ()                  # original (pre-unwrap) args
    donate_argnums: tuple = ()
    donated_invars: Any = None        # list[bool] over flat invars
    grad: Any = None                  # {"jaxpr", "names", "trainable"}
    counters: Any = None              # monitor.all_stats() snapshot
    retrace_sites: Any = None         # trace_probe.snapshot()


# ---------------------------------------------------------------------------
# jaxpr utilities shared by the passes
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr        # ClosedJaxpr
            elif hasattr(x, "eqns"):
                yield x              # raw Jaxpr


def iter_eqns(jaxpr) -> Iterable:
    """Yield every eqn of ``jaxpr`` recursively, descending into
    call/control-flow sub-jaxprs (pjit, scan, while, cond, custom_vjp)."""
    if hasattr(jaxpr, "jaxpr"):      # ClosedJaxpr -> Jaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def eqn_source(eqn) -> Optional[str]:
    """'file:line (fn)' provenance of one eqn, best-effort across jax
    versions. The analyzer's own tracing wrappers are not provenance."""
    try:
        from jax._src import source_info_util
        s = source_info_util.summarize(eqn.source_info)
        return None if "paddle_tpu/analysis" in s else s
    except Exception:
        return None


_TRANSPARENT = frozenset({
    "broadcast_in_dim", "convert_element_type", "reshape", "squeeze",
    "transpose", "copy", "expand_dims", "stop_gradient",
})


def is_structural_zero(jaxpr, var) -> bool:
    """True when ``var`` is produced by a chain of shape/dtype-only ops
    terminating in a literal 0 — the exact way jax AD materializes a
    symbolic-zero cotangent (``broadcast_in_dim [0.0]``). Constant but
    NONzero values (e.g. the grad of ``p.sum()``) are not zeros, so a
    linear loss never false-positives."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    producers = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producers[ov] = eqn
    for _ in range(64):  # chain bound; real zero chains are 1-2 eqns
        if hasattr(var, "val"):  # Literal
            try:
                return not np.any(np.asarray(var.val))
            except Exception:
                return False
        eqn = producers.get(var)
        if eqn is None or eqn.primitive.name not in _TRANSPARENT:
            return False
        var = eqn.invars[0]
    return False


# ---------------------------------------------------------------------------
# tracing helpers
# ---------------------------------------------------------------------------

def _concretization_errors():
    import jax.errors as je
    return tuple(
        getattr(je, n) for n in
        ("ConcretizationTypeError", "TracerArrayConversionError",
         "TracerBoolConversionError", "TracerIntegerConversionError")
        if hasattr(je, n))


def _blame_frame(exc) -> Optional[str]:
    """Deepest traceback frame that is user code — not jax internals,
    not this package — so a ConcretizationError points at the
    ``.numpy()`` call site, not at jax's tracer plumbing."""
    frames = _tb.extract_tb(exc.__traceback__)

    def is_jax(f):
        return "/jax/" in f or "/jax_" in f or "/jaxlib/" in f \
            or "/site-packages/jax" in f

    def is_ours(f):
        return "paddle_tpu/analysis" in f

    best = None
    for fr in frames:
        if is_jax(fr.filename) or is_ours(fr.filename):
            continue
        best = fr  # keep the deepest acceptable frame
    # prefer a frame OUTSIDE the framework itself when one exists (the
    # user's line beats framework/tensor.py's np.asarray internals)
    user = None
    for fr in frames:
        if is_jax(fr.filename) or is_ours(fr.filename) \
                or "paddle_tpu/" in fr.filename:
            continue
        user = fr
    fr = user or best
    if fr is None:
        return None
    return f"{fr.filename}:{fr.lineno} ({fr.name})"


def _tensor_type():
    from ..framework.tensor import Tensor
    return Tensor


def _trace_callable(fn, args, static_argnums=()):
    """make_jaxpr over ``fn`` with Tensor-aware arg/result handling.
    Returns (closed_jaxpr, donated_invars, arg_leaf_ranges)."""
    import jax

    Tensor = _tensor_type()
    static_argnums = tuple(static_argnums)
    dyn = [a for i, a in enumerate(args) if i not in static_argnums]
    statics = {i: a for i, a in enumerate(args) if i in static_argnums}

    is_t = lambda x: isinstance(x, Tensor)
    flat, treedef = jax.tree_util.tree_flatten(tuple(dyn), is_leaf=is_t)
    mask = [is_t(x) for x in flat]
    leaves = [x._data if m else x for x, m in zip(flat, mask)]

    # per-ORIGINAL-arg leaf ranges (None for static args) so
    # donate_argnums — which live in the same index space jax.jit uses,
    # counting statics — map onto flat invar positions correctly even
    # with a static argnum before a donated one
    ranges = []
    pos = 0
    for i, a in enumerate(args):
        if i in statics:
            ranges.append(None)
            continue
        n = len(jax.tree_util.tree_flatten(a, is_leaf=is_t)[0])
        ranges.append((pos, pos + n))
        pos += n

    def unwrap(x):
        return x._data if isinstance(x, Tensor) else x

    def fn_flat(*xs):
        rewrapped = [Tensor(x, stop_gradient=True) if m else x
                     for x, m in zip(xs, mask)]
        call_dyn = list(jax.tree_util.tree_unflatten(treedef, rewrapped))
        call_args = []
        di = 0
        for i in range(len(args)):
            if i in statics:
                call_args.append(statics[i])
            else:
                call_args.append(call_dyn[di])
                di += 1
        out = fn(*call_args)
        return jax.tree_util.tree_map(unwrap, out, is_leaf=is_t)

    closed = jax.make_jaxpr(fn_flat)(*leaves)
    return closed, ranges


def _donated_invars(closed, donate_argnums, ranges):
    """Donation mask over the outer jaxpr's invars: the explicit
    donate_argnums argument wins; otherwise auto-detect a single
    top-level jit eqn's donated_invars (analyzing an already-jitted fn
    sees its donation contract without being told)."""
    n = len(closed.jaxpr.invars)
    if donate_argnums:
        mask = [False] * n
        for argnum in donate_argnums:
            if argnum < len(ranges) and ranges[argnum] is not None:
                lo, hi = ranges[argnum]
                for i in range(lo, min(hi, n)):
                    mask[i] = True
        return mask
    eqns = closed.jaxpr.eqns
    if len(eqns) == 1 and eqns[0].primitive.name == "jit":
        don = eqns[0].params.get("donated_invars")
        if don and any(don):
            # map the jit eqn's donated invars back onto outer invars
            outer = {v: i for i, v in enumerate(closed.jaxpr.invars)}
            mask = [False] * n
            for v, d in zip(eqns[0].invars, don):
                if d and v in outer:
                    mask[outer[v]] = True
            return mask
    return None


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

def _is_program(target) -> bool:
    return hasattr(target, "_forward_env") and hasattr(target, "_nodes")


def _program_callable(program):
    """A pure (feeds, params) -> outputs replay of a captured static
    Program, traceable without executing (the Executor pre-flight)."""
    import jax.numpy as jnp

    feed_avals = {}
    for name, tid in program._feeds.items():
        t = program._vars[tid]
        feed_avals[name] = jnp.zeros(tuple(t._data.shape), t._data.dtype)
    params = {n: p._data for n, p in program._params.items()}

    def replay(feeds, params):
        env = program._forward_env(feeds, params)
        # every produced value is a root: nothing gets pruned, so the
        # passes see the whole recorded graph
        return [env[tid] for node in program._nodes
                for tid in node.out_ids if tid in env]

    return replay, (feed_avals, params)


def _translated_callable(layer):
    """Trace a jit.load artifact (TranslatedLayer) from its saved specs."""
    import jax

    avals = []
    for s in layer.input_specs:
        shape = tuple(1 if d in (-1, None) else int(d)
                      for d in s.get("shape", ()))
        avals.append(jax.ShapeDtypeStruct(shape, np.dtype(
            s.get("dtype", "float32"))))
    if not avals:
        raise ValueError(
            "saved artifact has no input_specs metadata; pass avals "
            "explicitly: analyze(layer._exported.call, *avals)")
    return layer._exported.call, tuple(avals)


def analyze(target, *args, donate_argnums=(), static_argnums=(),
            passes: Optional[Sequence[str]] = None, name: Optional[str]
            = None, grad: Any = None) -> Report:
    """Trace ``target`` (callable, jitted callable, captured static
    Program, or jit.load TranslatedLayer) and run the analysis pass
    pipeline over the resulting jaxpr WITHOUT compiling or executing it.

    ``args`` are example inputs — Tensors, arrays or ShapeDtypeStructs
    (ignored for Programs, which carry their own feed specs).
    ``donate_argnums`` declares the donation contract to the
    donation-safety pass (auto-detected from an already-jitted target).
    ``grad`` optionally supplies {"jaxpr", "names", "trainable"} for the
    dead/frozen-grad pass (see ``analyze_model``, which builds it from a
    hapi Model). Returns a :class:`Report`; never executes device code.
    """
    from ..framework import trace_probe
    from ..framework.monitor import all_stats

    t_run = time.perf_counter()
    if _is_program(target):
        fn, fn_args = _program_callable(target)
        tname = name or "static.Program"
        donate_argnums = ()
    elif hasattr(target, "_exported") and hasattr(target, "input_specs"):
        fn, fn_args = _translated_callable(target)
        tname = name or "jit.load artifact"
    elif callable(target) or target is None:
        fn, fn_args = target, args
        tname = name or getattr(target, "__name__", None) or repr(target)
    else:
        raise TypeError(f"cannot analyze {type(target).__name__}")

    ctx = AnalysisContext(target_name=tname, args=fn_args,
                          donate_argnums=tuple(donate_argnums),
                          grad=grad, counters=all_stats(),
                          retrace_sites=trace_probe.snapshot())
    report = Report(target=tname)

    if fn is not None:
        with _prof.record(f"analysis/trace/{tname}", "analysis"):
            try:
                closed, ranges = _trace_callable(fn, fn_args,
                                                 static_argnums)
                ctx.closed_jaxpr = closed
                ctx.donated_invars = _donated_invars(
                    closed, ctx.donate_argnums, ranges)
                report.n_eqns = sum(1 for _ in iter_eqns(closed))
            except _concretization_errors() as e:
                ctx.trace_error = e
                ctx.trace_error_source = _blame_frame(e)

    selected = list(passes) if passes is not None else list(_PASSES)
    for pid in selected:
        p = _PASSES.get(pid)
        if p is None:
            raise KeyError(f"unknown analysis pass {pid!r}; "
                           f"registered: {all_passes()}")
        t0 = time.perf_counter()
        found = list(p(ctx))
        stat_observe(f"analysis/pass_ms/{pid}",
                     (time.perf_counter() - t0) * 1e3)
        report.passes_run.append(pid)
        report.findings.extend(found)

    stat_add("analysis/runs")
    stat_add("analysis/findings", len(report.findings))
    for f in report.findings:
        stat_add(f"analysis/findings/{f.severity}")
        stat_add(f"analysis/findings/{f.pass_id}")
    stat_observe("analysis/analyze_ms",
                 (time.perf_counter() - t_run) * 1e3)
    return report
