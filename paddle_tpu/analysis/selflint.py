"""Repo self-lint: AST rules over ``paddle_tpu/`` itself.

The jaxpr passes check programs USERS build; these rules check the
framework's own source for the contracts the codebase documents but
Python cannot enforce (≙ the reference's tools/codestyle custom checks
+ cpplint rules for its own invariants):

* ``device-get-hot-path`` — no bare ``jax.device_get`` in hot-path
  modules (dispatch, tensor, monitor, the hapi step loop): every one is
  a blocking D2H sync per call. Sync points elsewhere (spmd state
  mirror, pipeline aggregation) are legitimate and stay unflagged.
* ``monitor-lock-contract`` — the monitor's writer hot path is lock-free
  BY CONTRACT (framework/monitor.py docstring): ``stat_add`` must not
  take ``_lock``, and no module outside monitor.py may import or touch
  its ``_lock``/``_stats``/``_hists`` internals.
* ``asarray-on-traced`` — inside a ``@register_op`` impl (which runs
  under jit unless registered ``jit=False``), ``np.asarray``/``np.array``
  on an op argument concretizes a tracer: TracerArrayConversionError at
  best, a silent constant-bake at worst. Nested host-callback bodies
  (pure_callback closures) shadow the name and are exempt.
* ``serving-host-sync`` — the continuous-batching decode loop
  (``paddle_tpu/serving/``, the paged memory manager ``serving/paging.py``
  included) must stay sync-free: ``jax.device_get``,
  ``.block_until_ready()`` (method or ``jax.block_until_ready`` module
  form) and ``.numpy()`` anywhere in the package are a per-step device
  stall. The single argued exception is the windowed token fetch
  (``serving/scheduler.py _fetch``), which carries the suppression.
* ``ops-handler-sync`` — the ops HTTP surface (``serving/opsserver.py``),
  the SLO plane (``serving/slo.py``) and the inference front door
  (``serving/frontdoor.py``) are scrape-only BY CONTRACT:
  handlers serve collector samples, host rings and host counters, and
  must never touch the device or block on the scheduler. On top of the
  ``serving-host-sync`` walk (which already covers both files as part
  of the package), this rule bans ANY ``jax.*``/``jnp.*`` call and the
  scheduler-blocking reads ``.result()``/``.item()`` there — a scrape
  that blocks on a stuck scheduler turns the monitoring plane into a
  second victim of the outage it exists to observe.
* ``memory-stats-hot-path`` — ``memory_stats()`` polling (a PjRt query
  per call) stays OFF the scheduler hot path: inside ``serving/`` the
  memory timeline is fed by host-only ``profiler.memory.mark()``
  stamps; device polling belongs to the tracker's background sampler
  thread (``profiler/memory.py``) and windowed surfaces like fit's
  flush.
* ``numerics-host-sync`` — the training numerics layer
  (``profiler/numerics.py``) exists to REPLACE the reference's per-op
  host sweep with audits fetched only at fit's flush windows, so the
  module itself must never sync: ``jax.device_get``, ``.item()``,
  ``.numpy()`` and ``.block_until_ready()`` are banned there — the
  fetch lives in ``hapi/model.py _flush_window`` (behind the window's
  existing blocking loss fetch), and the recorder receives numpy.
* ``pallas-block-tiling`` — Mosaic's TPU block-shape rule, statically:
  inside ``ops/``, a ``pl.BlockSpec`` whose block tuple carries a
  LITERAL second-to-last dim not divisible by 8, or a literal last dim
  neither divisible by 128 nor >= 8-aligned... — precisely: the
  second-to-last block dim must be divisible by 8 (or equal the array
  dim) and the last must be 128-aligned (or the full array dim). The
  AST cannot see array shapes, so literal dims that fail the divisible
  test are flagged and a spec that is legal because the block IS the
  full array dim carries a ``# lint: ok`` suppression with the argument
  adjacent. This is the ``(1, 128)``-block crash once recorded on
  hardware (flash-attention LSE output), turned into a
  standing static check. SMEM specs and shapeless (whole-array) specs
  are exempt; dynamic dims (names/expressions) are trusted — the
  kernels derive them from array shapes.

* ``metric-naming`` — literal metric names at monitor
  (``stat_add``/``stat_observe``) and metrics-registry
  (``metrics.inc``/``observe``/``set_gauge``) write sites are lowercase
  snake_case path segments, and a name that says it carries time or
  size says the unit: ``_ms``/``_bytes``, never ``_time``/``_secs``/
  ``_mb``. One process's metrics feed one Grafana; a ``*_secs`` sample
  landing in a ``*_ms`` panel misreads by 1000x and a CamelCase name
  breaks every PromQL regex written against the snake_case rest.

* ``analysis-no-device`` — the static planner (``paddle_tpu/analysis/``)
  answers "will it fit?" BEFORE any compile, from jaxpr avals alone
  (ISSUE 18): ``jax.jit``, ``.compile()`` (``re.compile`` exempt),
  ``device_put`` and ``block_until_ready`` are banned in the package —
  an admission gate that compiles has already paid the cost it gates.

Suppress a finding with a trailing ``# lint: ok`` comment on the line
(used only where a human has argued the exception in an adjacent
comment). Run: ``python -m paddle_tpu.analysis --selflint`` or the
tier-1 test (tests/test_selflint.py).
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import List, Optional, Sequence

__all__ = ["LintFinding", "lint_source", "lint_repo", "HOT_PATH_MODULES"]

# modules where a stray device_get is a per-call sync on the hot path
HOT_PATH_MODULES = (
    "framework/dispatch.py", "framework/tensor.py", "framework/monitor.py",
    "framework/trace_probe.py", "hapi/model.py", "ops/registry.py",
)

_MONITOR_PRIVATE = {"_lock", "_stats", "_hists"}


@dataclass
class LintFinding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _suppressed(source_lines: Sequence[str], lineno: int) -> bool:
    try:
        return "# lint: ok" in source_lines[lineno - 1]
    except IndexError:
        return False


def _is_jax_device_get(node: ast.Call) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr == "device_get"
            and isinstance(f.value, ast.Name) and f.value.id == "jax")


def _decorator_name(d) -> Optional[str]:
    if isinstance(d, ast.Call):
        d = d.func
    if isinstance(d, ast.Attribute):
        return d.attr
    if isinstance(d, ast.Name):
        return d.id
    return None


def _op_decorator(fn: ast.FunctionDef):
    """The @register_op(...) decorator Call of ``fn``, if any."""
    for d in fn.decorator_list:
        if _decorator_name(d) in ("register_op", "register_override") \
                and isinstance(d, ast.Call):
            return d
    return None


def _jit_disabled(dec: ast.Call) -> bool:
    for kw in dec.keywords:
        if kw.arg == "jit" and isinstance(kw.value, ast.Constant) \
                and kw.value.value is False:
            return True
    return False


class _AsarrayVisitor(ast.NodeVisitor):
    """Flags np.asarray/np.array(<op param>) inside an op impl, honoring
    nested-function shadowing (host-callback closures redefine the
    name, which makes the call host-side and fine)."""

    def __init__(self, params, lines, path, findings):
        self.scopes = [set(params)]
        self.lines = lines
        self.path = path
        self.findings = findings

    def _params_of(self, node):
        a = node.args
        names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return set(names)

    def visit_FunctionDef(self, node):
        self.scopes.append(self._params_of(node))
        self.generic_visit(node)
        self.scopes.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self.scopes.append(self._params_of(node))
        self.generic_visit(node)
        self.scopes.pop()

    def visit_Call(self, node):
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr in ("asarray", "array")
                and isinstance(f.value, ast.Name)
                and f.value.id in ("np", "numpy") and node.args
                and isinstance(node.args[0], ast.Name)):
            name = node.args[0].id
            # flagged only when the name is the OP's own parameter and no
            # nested scope shadows it
            if name in self.scopes[0] and not any(
                    name in s for s in self.scopes[1:]) \
                    and not _suppressed(self.lines, node.lineno):
                self.findings.append(LintFinding(
                    "asarray-on-traced", self.path, node.lineno,
                    f"np.{f.attr}({name}) on a traced op argument — "
                    f"concretizes under jit; use jnp, mark the op "
                    f"jit=False, or route through pure_callback"))
        self.generic_visit(node)


def _blockspec_literal_dims(node: ast.Call):
    """For a ``BlockSpec(...)`` call (attribute or bare-name form, the
    block tuple positional or via ``block_shape=``): the shape tuple's
    last two elements as ints where they are literals (None where
    dynamic), or None when the spec has no block tuple / is
    SMEM-space."""
    f = node.func
    name = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else None)
    if name != "BlockSpec":
        return None
    shape = node.args[0] if node.args else None
    for kw in node.keywords:
        if kw.arg == "memory_space" and isinstance(kw.value, ast.Attribute) \
                and kw.value.attr == "SMEM":
            return None            # scalar memory: no (8, 128) tiling
        if kw.arg == "block_shape" and shape is None:
            shape = kw.value
    if not isinstance(shape, ast.Tuple) or len(shape.elts) < 2:
        return None

    def lit(e):
        return e.value if isinstance(e, ast.Constant) \
            and isinstance(e.value, int) else None

    return lit(shape.elts[-2]), lit(shape.elts[-1])


# metric-emitting call sites the metric-naming rule inspects: the
# monitor writers anywhere, and the metrics-registry writers when
# called through a module alias that names the registry
_MONITOR_WRITERS = ("stat_add", "stat_observe")
_REGISTRY_WRITERS = ("inc", "set_gauge", "observe")
# a name part ending in one of these carries a time/size quantity with
# NO unit: the naming contract wants _ms / _bytes so dashboards never
# have to guess (and never mix seconds into a *_ms panel)
_UNITLESS_TIME_SUFFIXES = ("_time", "_latency", "_duration", "_secs",
                           "_seconds")
_NON_BYTE_SIZE_SUFFIXES = ("_kb", "_mb", "_gb", "_kib", "_mib", "_gib")
_METRIC_CHARSET = frozenset("abcdefghijklmnopqrstuvwxyz0123456789_/")


def _metric_leading_literal(arg) -> "Optional[tuple]":
    """(leading_literal, is_full_literal) of a metric-name argument, or
    None when nothing literal leads it (a fully dynamic name is the
    caller's problem — the registry validates at write time)."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value, True
    if isinstance(arg, ast.JoinedStr) and arg.values:
        head = arg.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return head.value, False
    return None


def _metric_name_finding(node: ast.Call) -> Optional[str]:
    """The metric-naming rule body: literal metric names at monitor /
    registry write sites must be lowercase snake_case path segments
    (``[a-z0-9_/]``; dimensions belong in labels or the per-key path
    tail, units in a ``_ms``/``_bytes`` suffix), and a name that SAYS
    it carries time or size must say the unit (``op_time`` -> error,
    ``op_time_ms`` -> fine; ``_gb`` -> ``_bytes``)."""
    f = node.func
    fname = f.attr if isinstance(f, ast.Attribute) else \
        (f.id if isinstance(f, ast.Name) else None)
    if fname in _MONITOR_WRITERS:
        pass
    elif fname in _REGISTRY_WRITERS:
        # only when addressed through a metrics-registry alias —
        # .observe()/.inc() are common method names elsewhere
        v = getattr(f, "value", None)
        if not (isinstance(v, ast.Name) and "metric" in v.id.lower()):
            return None
    else:
        return None
    if not node.args:
        return None
    lit = _metric_leading_literal(node.args[0])
    if lit is None:
        return None
    text, full = lit
    bad = sorted({c for c in text if c not in _METRIC_CHARSET})
    if bad:
        return (f"metric name {text!r} violates the naming contract "
                f"(snake_case [a-z0-9_] path segments; offending "
                f"chars: {''.join(bad)!r}) — dimensions go in labels "
                f"or the per-key path tail, never CamelCase/-/spaces")
    if full:
        tail = text.rsplit("/", 1)[-1]
        for suf in _UNITLESS_TIME_SUFFIXES:
            if tail.endswith(suf):
                return (f"metric name {text!r} carries a time quantity "
                        f"without its unit: suffix it _ms (the naming "
                        f"contract — a *_secs sample in a *_ms panel "
                        f"is a 1000x lie)")
        for suf in _NON_BYTE_SIZE_SUFFIXES:
            if tail.endswith(suf):
                return (f"metric name {text!r} bakes a scaled size unit "
                        f"into the name: record raw _bytes and let the "
                        f"dashboard scale")
    return None


def lint_source(path: str, source: str, relpath: str) -> List[LintFinding]:
    """Lint one file's source. ``relpath`` is the path relative to the
    package root (rule applicability is keyed on it)."""
    findings: List[LintFinding] = []
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [LintFinding("parse", path, e.lineno or 0, str(e))]
    lines = source.splitlines()
    rel = relpath.replace(os.sep, "/")
    in_monitor = rel.endswith("framework/monitor.py")
    hot = any(rel.endswith(m) for m in HOT_PATH_MODULES)
    # the serving PACKAGE only — inference/serving.py (the gather-and-run
    # batcher) blocks its callers by design and is not in scope
    in_serving = rel.startswith("serving/")
    # the scrape-only ops surface: HTTP handlers + the SLO plane
    in_ops_surface = rel.endswith("serving/opsserver.py") \
        or rel.endswith("serving/slo.py") \
        or rel.endswith("serving/frontdoor.py")
    # Pallas kernels live in ops/ — BlockSpec tiling is checked there
    in_ops = rel.startswith("ops/")
    # the numerics audit module: host-pure over numpy BY CONTRACT
    in_numerics = rel.endswith("profiler/numerics.py")
    # the static planner: aval arithmetic only, never compile/device work
    in_analysis = rel.startswith("analysis/")

    for node in ast.walk(tree):
        # rule: analysis-no-device (the planner's fit-BEFORE-compile
        # contract: paddle_tpu/analysis/ answers memory questions from
        # jaxprs alone, so nothing in the package may trigger a compile
        # or touch the device)
        if in_analysis and isinstance(node, ast.Call):
            f = node.func
            banned = None
            if isinstance(f, ast.Attribute):
                recv = f.value
                recv_name = recv.id if isinstance(recv, ast.Name) else None
                if f.attr == "jit" and recv_name == "jax":
                    banned = "jax.jit"
                elif f.attr == "device_put":
                    banned = "device_put"
                elif f.attr == "block_until_ready":
                    banned = ".block_until_ready()"
                elif f.attr == "compile" and recv_name != "re":
                    banned = ".compile()"
            elif isinstance(f, ast.Name) and f.id == "device_put":
                banned = "device_put"
            if banned and not _suppressed(lines, node.lineno):
                findings.append(LintFinding(
                    "analysis-no-device", path, node.lineno,
                    f"{banned} inside paddle_tpu/analysis/: the static "
                    f"planner answers fit-BEFORE-compile from jaxpr "
                    f"avals alone — compiling or touching the device "
                    f"here would make the admission gate pay the cost "
                    f"it exists to avoid"))
        # rule: pallas-block-tiling (Mosaic (8, 128) block-shape law)
        if in_ops and isinstance(node, ast.Call):
            dims = _blockspec_literal_dims(node)
            if dims is not None and not _suppressed(lines, node.lineno):
                sub, lane = dims
                if sub is not None and (sub < 1 or sub % 8):
                    findings.append(LintFinding(
                        "pallas-block-tiling", path, node.lineno,
                        f"BlockSpec second-to-last block dim {sub} is "
                        f"not divisible by 8: Mosaic rejects the layout "
                        f"on TPU (the (1, 128) block crash) unless "
                        f"it equals the array dim — if it provably "
                        f"does, argue it in an adjacent comment and "
                        f"suppress with '# lint: ok'"))
                if lane is not None and (lane < 1 or lane % 128):
                    findings.append(LintFinding(
                        "pallas-block-tiling", path, node.lineno,
                        f"BlockSpec last block dim {lane} is not "
                        f"128-aligned: Mosaic rejects the layout on TPU "
                        f"unless it equals the array dim — if it "
                        f"provably does, argue it in an adjacent "
                        f"comment and suppress with '# lint: ok'"))
        # rule: serving-host-sync (no host sync in the decode loop)
        if in_serving and isinstance(node, ast.Call):
            sync = None
            if _is_jax_device_get(node):
                sync = "jax.device_get"
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "block_until_ready" \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "jax":
                sync = "jax.block_until_ready"
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("block_until_ready", "numpy"):
                sync = f".{node.func.attr}()"
            if sync and not _suppressed(lines, node.lineno):
                findings.append(LintFinding(
                    "serving-host-sync", path, node.lineno,
                    f"{sync} in the serving package: the continuous-"
                    f"batching decode loop must stay async — route "
                    f"device reads through the single windowed fetch "
                    f"(serving/scheduler.py _fetch)"))
        # rule: ops-handler-sync (the scrape-only ops surface: no
        # device work, no scheduler-blocking reads — a monitoring
        # plane that blocks on what it monitors goes down with it)
        if in_ops_surface and isinstance(node, ast.Call):
            f = node.func
            bad = None
            if isinstance(f, ast.Attribute) \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id in ("jax", "jnp"):
                bad = f"{f.value.id}.{f.attr}"
            elif isinstance(f, ast.Attribute) \
                    and f.attr in ("result", "item", "block_until_ready",
                                   "numpy", "device_get"):
                bad = f".{f.attr}()"
            elif isinstance(f, ast.Name) and f.id == "device_get":
                bad = "device_get"
            if bad and not _suppressed(lines, node.lineno):
                findings.append(LintFinding(
                    "ops-handler-sync", path, node.lineno,
                    f"{bad} on the ops HTTP surface: handlers are "
                    f"scrape-only — no device fetches, no "
                    f"block_until_ready, no scheduler-blocking "
                    f"result()/item(); serve collector samples and "
                    f"host rings instead"))
        # rule: numerics-host-sync (the numerics audit module never
        # syncs — fetches belong to fit's flush window)
        if in_numerics and isinstance(node, ast.Call):
            sync = None
            if _is_jax_device_get(node):
                sync = "jax.device_get"
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "block_until_ready" \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "jax":
                sync = "jax.block_until_ready"
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("block_until_ready", "numpy",
                                           "item"):
                sync = f".{node.func.attr}()"
            if sync and not _suppressed(lines, node.lineno):
                findings.append(LintFinding(
                    "numerics-host-sync", path, node.lineno,
                    f"{sync} in the numerics audit module: the audit "
                    f"replaces the reference's per-op host sweep "
                    f"precisely by never syncing — device vectors are "
                    f"fetched ONLY at Model._flush_window (behind the "
                    f"window's existing loss fetch) and arrive here as "
                    f"numpy"))
        # rule: memory-stats-hot-path (no device memory polling in the
        # serving package — marks are host-only, the sampler thread
        # polls)
        if in_serving and isinstance(node, ast.Call):
            f = node.func
            poll = (isinstance(f, ast.Attribute)
                    and f.attr == "memory_stats") or \
                   (isinstance(f, ast.Name) and f.id == "memory_stats")
            if poll and not _suppressed(lines, node.lineno):
                findings.append(LintFinding(
                    "memory-stats-hot-path", path, node.lineno,
                    "memory_stats() polled in the serving package: a "
                    "PjRt stats query per scheduler cycle — stamp "
                    "host-only watermarks with profiler.memory.mark() "
                    "and leave polling to the tracker's sampler thread "
                    "(profiler/memory.py)"))
        # rule: device-get-hot-path
        if hot and isinstance(node, ast.Call) and _is_jax_device_get(node) \
                and not _suppressed(lines, node.lineno):
            findings.append(LintFinding(
                "device-get-hot-path", path, node.lineno,
                "bare jax.device_get in a hot-path module: a blocking "
                "D2H sync per call — return device values and flush in "
                "windows (Model._flush_window)"))

        # rule: monitor-lock-contract (outside monitor.py: no touching
        # its private state)
        if not in_monitor:
            bad = None
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.rsplit(".", 1)[-1] == "monitor":
                hit = [a.name for a in node.names
                       if a.name in _MONITOR_PRIVATE]
                bad = hit[0] if hit else None
            elif isinstance(node, ast.Attribute) \
                    and node.attr in _MONITOR_PRIVATE \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "monitor":
                bad = node.attr
            if bad and not _suppressed(lines, node.lineno):
                findings.append(LintFinding(
                    "monitor-lock-contract", path, node.lineno,
                    f"direct use of monitor.{bad}: the monitor's "
                    f"internals are private to its threading contract "
                    f"(framework/monitor.py docstring); use the "
                    f"stat_*/all_* API"))

        # rule: monitor-lock-contract (inside monitor.py: stat_add stays
        # lock-free)
        if in_monitor and isinstance(node, ast.FunctionDef) \
                and node.name == "stat_add":
            for sub in ast.walk(node):
                if isinstance(sub, ast.With) and any(
                        isinstance(item.context_expr, ast.Name)
                        and item.context_expr.id == "_lock"
                        for item in sub.items) \
                        and not _suppressed(lines, sub.lineno):
                    findings.append(LintFinding(
                        "monitor-lock-contract", path, sub.lineno,
                        "stat_add takes _lock: the writer hot path is "
                        "lock-free BY CONTRACT (module docstring) — a "
                        "lock per eager op dispatch serializes the "
                        "engine"))

        # rule: metric-naming (snake_case paths, unit-suffixed units)
        if isinstance(node, ast.Call):
            mfind = _metric_name_finding(node)
            if mfind and not _suppressed(lines, node.lineno):
                findings.append(LintFinding(
                    "metric-naming", path, node.lineno, mfind))

        # rule: asarray-on-traced (op impls that run under jit)
        if isinstance(node, ast.FunctionDef):
            dec = _op_decorator(node)
            if dec is not None and not _jit_disabled(dec):
                params = [p.arg for p in node.args.posonlyargs
                          + node.args.args]
                v = _AsarrayVisitor(params, lines, path, findings)
                for stmt in node.body:  # not node: the op fn's own
                    v.visit(stmt)       # params are scope 0, not a shadow

    return findings


def lint_repo(root: Optional[str] = None) -> List[LintFinding]:
    """Lint every .py file under the paddle_tpu package (or ``root``)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings: List[LintFinding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            findings.extend(lint_source(path, src, rel))
    return findings
