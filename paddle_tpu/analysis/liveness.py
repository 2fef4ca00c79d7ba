"""Donation-aware static liveness analysis over jaxprs (ISSUE 18).

The reference stack answers "will it fit?" only AFTER paying a compile
(`memory_optimize` / the inplace pass run on the fully built
ProgramDesc) or after an OOM postmortem. Here the question is answered
on the jaxpr we already trace for the PR-3 analysis passes: a linear
liveness scan over program order computes, at every equation, the bytes
that must be resident — pinned inputs, donated inputs still awaiting
their last use, intermediates between production and last consumption,
and outputs from production to program end — and reports the maximum as
``static_peak_bytes`` together with a top-k timeline of the fattest
program points, each blamed to user source via the PR-3
``eqn_source`` machinery.

The model (documented so the cross-check tolerance is auditable):

* **non-donated invars and constvars are pinned** for the whole
  program — jit may not overwrite caller buffers;
* **donated invars die at their last use** — XLA may then reuse the
  buffer (an invar that is also an output stays pinned);
* **intermediates live** from the eqn that produces them to their last
  consuming eqn; results unused later are charged at their producing
  point only (they materialize, then free);
* **outputs are pinned** from their producing eqn to program end;
* **updates run in place**: a ``scatter`` / ``dynamic_update_slice``
  whose updated operand is freeable and dies at that eqn reuses the
  operand's buffer for its result (what XLA does with a donated KV
  pool or cache) — charging both would bill every pool twice and
  refuse any engine whose pool takes more than half the device. The
  same holds for a ``pallas_call`` output aliased to an operand
  (``input_output_aliases``: the cache append, ``ops/kv_append.py``),
  and for a call (pjit) whose inner program makes its result that way
  from one of its arguments — jit inlines the call, so the caller's
  dying buffer is the one updated;
* **a Pallas kernel's body is not walked**: its variables are refs into
  buffers already counted at the call, VMEM scratch and tiles in
  registers — nothing in it is an HBM allocation;
* **sub-jaxprs** (pjit / shard_map / scan / while / cond /
  custom_vjp) are walked recursively: the inner program's peak is
  charged at the calling eqn with the operand/result bytes already
  counted in the outer frame discounted, and exclusive branches
  (cond) contribute their max, not their sum. ``shard_map`` bodies
  carry PER-DEVICE avals, so recursion prices the sharded interior
  correctly while the outer (global-shape) operands remain the
  replicated upper bound.

This is a NO-FUSION upper-bound estimator: XLA's fusion and buffer
aliasing can only shrink the real footprint below it, while the real
peak can exceed only by workspace XLA adds (convolution scratch,
collective staging). ``CROSSCHECK_RTOL`` documents the bracket the
dry-run asserts against ``memory_analysis()`` where the backend
reports figures; where it does not, fields stay ``None`` — never a
fake number.

Everything here is host arithmetic over avals. The module must never
compile or touch the device — enforced by the ``analysis-no-device``
self-lint rule.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence

import numpy as np

from .core import _donated_invars, _trace_callable, eqn_source

__all__ = [
    "aval_bytes", "jaxpr_liveness", "callable_liveness",
    "donation_misses", "crosscheck", "PeakPoint", "LivenessReport",
    "CROSSCHECK_RTOL", "DONATION_MISS_MIN_BYTES",
]

# The documented cross-check bracket (see module docstring): with
# xla = argument + temp + output - alias (memory_analysis()'s resident
# footprint, donated aliases counted once), the dry-run asserts
#   xla / CROSSCHECK_RTOL  <=  static_peak_bytes
#   static_peak_bytes      <=  xla * CROSSCHECK_RTOL
# 4x absorbs fusion on the low side (XLA eliding intermediates the
# no-fusion model charges) and padding/workspace on the high side.
CROSSCHECK_RTOL = 4.0

# donation-miss pass floor: invars below this are not worth a finding
# (donating a few KiB buys nothing on any real device).
DONATION_MISS_MIN_BYTES = 1 << 20


def aval_bytes(aval) -> int:
    """Bytes one materialized value of ``aval`` occupies; 0 for
    tokens/refs/symbolic shapes (best-effort, never raises)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    try:
        for d in shape:
            n *= int(d)
        return n * np.dtype(dtype).itemsize
    except Exception:
        return 0


@dataclass
class PeakPoint:
    """One program point of the liveness timeline."""

    index: int                  # position in traversal order
    primitive: str
    live_bytes: int
    source: Optional[str] = None
    depth: int = 0              # sub-jaxpr nesting depth

    def as_dict(self) -> dict:
        return {"index": self.index, "primitive": self.primitive,
                "live_bytes": self.live_bytes, "source": self.source,
                "depth": self.depth}


@dataclass
class LivenessReport:
    """Result of one liveness scan."""

    static_peak_bytes: int
    peak: Optional[PeakPoint]
    timeline: List[PeakPoint] = field(default_factory=list)  # top-k, fattest first
    arg_bytes: int = 0          # all top-level invars
    donated_bytes: int = 0      # donated subset of arg_bytes
    const_bytes: int = 0
    out_bytes: int = 0
    n_points: int = 0

    def as_dict(self) -> dict:
        return {
            "static_peak_bytes": self.static_peak_bytes,
            "peak": self.peak.as_dict() if self.peak else None,
            "timeline": [p.as_dict() for p in self.timeline],
            "arg_bytes": self.arg_bytes,
            "donated_bytes": self.donated_bytes,
            "const_bytes": self.const_bytes,
            "out_bytes": self.out_bytes,
            "n_points": self.n_points,
        }

    def table(self) -> str:
        lines = [f"static peak {self.static_peak_bytes:,} B over "
                 f"{self.n_points} program points "
                 f"(args {self.arg_bytes:,} B, {self.donated_bytes:,} B "
                 f"donated; outputs {self.out_bytes:,} B)"]
        for p in self.timeline:
            lines.append(f"  {p.live_bytes:>14,} B  {p.primitive:<20} "
                         f"{p.source or '-'}")
        return "\n".join(lines)


def _is_literal(v) -> bool:
    return hasattr(v, "val")


# primitives whose result reuses operand 0's buffer when that operand
# dies at the eqn (see "updates run in place" in the module docstring)
_IN_PLACE_UPDATES = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max",
    "dynamic_update_slice"})


def _in_place_pairs(eqn) -> List[tuple]:
    """``(operand index, result index)`` of every result of ``eqn`` that
    is written into an operand's buffer: an update primitive's operand
    0, a ``pallas_call``'s aliased operands, and — through a call with
    ONE inner program (pjit) — an inner result made that way directly
    from an inner argument."""
    name = eqn.primitive.name
    if name in _IN_PLACE_UPDATES:
        return [(0, 0)]
    if name == "pallas_call":
        return [(int(i), int(o))
                for i, o in eqn.params.get("input_output_aliases", ())]
    subs = list(_sub_jaxprs_raw(eqn))
    if len(subs) != 1 or len(subs[0].invars) != len(eqn.invars) \
            or len(subs[0].outvars) != len(eqn.outvars):
        return []
    sub = subs[0]
    arg_of = {v: k for k, v in enumerate(sub.invars)}
    out_of = {v: k for k, v in enumerate(sub.outvars) if not _is_literal(v)}
    pairs = []
    for inner in sub.eqns:
        for i, o in _in_place_pairs(inner):
            src, dst = inner.invars[i], inner.outvars[o]
            if not _is_literal(src) and src in arg_of and dst in out_of:
                pairs.append((arg_of[src], out_of[dst]))
    return pairs


def _walk(jaxpr, donated: Optional[Sequence[bool]], base: int,
          points: List[PeakPoint], depth: int) -> int:
    """Linear liveness scan over one (raw) jaxpr level. ``base`` is the
    byte load pinned by enclosing frames; returns the base-inclusive
    peak of this level and everything below it. Appends a PeakPoint
    per eqn (inner levels append their own)."""
    eqns = jaxpr.eqns
    n = len(eqns)

    last_use = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not _is_literal(v):
            last_use[v] = n              # pinned through program end

    live = {}                            # var -> bytes, freeable later
    pinned = 0
    for v in jaxpr.constvars:
        pinned += aval_bytes(v.aval)
    for k, v in enumerate(jaxpr.invars):
        b = aval_bytes(v.aval)
        lu = last_use.get(v)
        if donated is not None and k < len(donated) and donated[k] \
                and lu is not None and lu < n:
            live[v] = b                  # donated: frees after last use
        elif donated is not None and k < len(donated) and donated[k] \
                and lu is None:
            pass                         # dead donation: freeable at entry
        else:
            pinned += b                  # caller's buffer, pinned
    cur = pinned + sum(live.values())
    peak = base + cur
    if depth == 0:
        points.append(PeakPoint(len(points), "<args>", peak, None, depth))

    for i, eqn in enumerate(eqns):
        out_total = sum(aval_bytes(v.aval) for v in eqn.outvars
                        if not _is_literal(v))
        reused = set()                   # operands a result overwrites
        saved = 0
        for k, o in _in_place_pairs(eqn):
            target = eqn.invars[k]
            if not _is_literal(target) and target in live \
                    and last_use.get(target) == i and k not in reused:
                reused.add(k)
                saved += min(aval_bytes(eqn.outvars[o].aval), live[target])
        out_total -= min(out_total, saved)
        at_point = base + cur + out_total
        subs = [] if eqn.primitive.name == "pallas_call" \
            else [x for x in _sub_jaxprs_raw(eqn)]
        inner_peak = 0
        if subs:
            don_inner = eqn.params.get("donated_invars") \
                if len(subs) == 1 else None
            if reused and len(subs) == 1:
                # the inner program may overwrite what dies at the call
                given = tuple(don_inner or ())
                don_inner = [k in reused or (k < len(given) and given[k])
                             for k in range(len(eqn.invars))]
            for sub in subs:
                io = sum(aval_bytes(v.aval) for v in sub.invars) + \
                     sum(aval_bytes(v.aval) for v in sub.outvars
                         if not _is_literal(v)) - saved
                inner_base = max(0, at_point - io)
                p = _walk(sub, don_inner, inner_base, points, depth + 1)
                inner_peak = max(inner_peak, p)   # exclusive branches: max
        points.append(PeakPoint(len(points), eqn.primitive.name,
                                at_point, eqn_source(eqn), depth))
        peak = max(peak, at_point, inner_peak)
        # free operands whose last use is here
        for v in eqn.invars:
            if not _is_literal(v) and v in live and last_use.get(v) == i:
                cur -= live.pop(v)
        # results used later become live; results never read again were
        # charged transiently at this point only
        for v in eqn.outvars:
            if _is_literal(v):
                continue
            lu = last_use.get(v)
            if lu is not None and lu > i and v not in live:
                b = aval_bytes(v.aval)
                live[v] = b
                cur += b
    return peak


def _sub_jaxprs_raw(eqn):
    """Raw sub-jaxprs of one eqn (ClosedJaxpr unwrapped) — the liveness
    twin of core._sub_jaxprs, kept here so the walk can pair each sub
    with the eqn's donation param."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def jaxpr_liveness(closed, donated_invars: Optional[Sequence[bool]] = None,
                   top_k: int = 8) -> LivenessReport:
    """Liveness scan over a ClosedJaxpr (or raw Jaxpr)."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    points: List[PeakPoint] = []
    peak_bytes = _walk(jaxpr, donated_invars, 0, points, 0)

    arg_bytes = sum(aval_bytes(v.aval) for v in jaxpr.invars)
    donated_bytes = 0
    if donated_invars is not None:
        donated_bytes = sum(
            aval_bytes(v.aval)
            for v, d in zip(jaxpr.invars, donated_invars) if d)
    const_bytes = sum(aval_bytes(v.aval) for v in jaxpr.constvars)
    out_bytes = sum(aval_bytes(v.aval) for v in jaxpr.outvars
                    if not _is_literal(v))

    peak_pt = max(points, key=lambda p: p.live_bytes) if points else None
    timeline = sorted(points, key=lambda p: -p.live_bytes)[:max(0, top_k)]
    return LivenessReport(
        static_peak_bytes=peak_bytes, peak=peak_pt, timeline=timeline,
        arg_bytes=arg_bytes, donated_bytes=donated_bytes,
        const_bytes=const_bytes, out_bytes=out_bytes,
        n_points=len(points))


def callable_liveness(fn, *args, donate_argnums=(), static_argnums=(),
                      top_k: int = 8) -> LivenessReport:
    """Trace ``fn(*args)`` (PR-3 Tensor-aware tracing, no compile, no
    device work) and run the liveness scan. Donation comes from the
    explicit ``donate_argnums`` or, for an already-jitted fn, from its
    pjit eqn's donation contract."""
    closed, ranges = _trace_callable(fn, args, static_argnums)
    donated = _donated_invars(closed, tuple(donate_argnums), ranges)
    return jaxpr_liveness(closed, donated, top_k=top_k)


def donation_misses(closed, donated_invars: Optional[Sequence[bool]] = None,
                    min_bytes: int = DONATION_MISS_MIN_BYTES,
                    max_candidates: int = 8) -> List[dict]:
    """Large non-donated invars that die before program end, each with
    the ``static_peak_bytes`` reduction donating it would buy (a
    liveness re-scan with the invar marked donated — honest, not a
    heuristic). Entries with zero saving are dropped: donating an
    input whose lifetime spans the peak buys nothing in this model.

    Also returns ``kind='dead'`` entries for donated invars the program
    never reads (the dead-donation contract violation this analysis
    supersedes from the old boolean check)."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    n = len(jaxpr.eqns)
    base = jaxpr_liveness(closed, donated_invars, top_k=1)

    last_use = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i
    outset = {id(v) for v in jaxpr.outvars if not _is_literal(v)}

    donated = list(donated_invars) if donated_invars is not None else \
        [False] * len(jaxpr.invars)
    out: List[dict] = []
    candidates = []
    for k, v in enumerate(jaxpr.invars):
        is_donated = k < len(donated) and donated[k]
        used = v in last_use
        if is_donated and not used:
            out.append({"kind": "dead", "argnum": k,
                        "bytes": aval_bytes(v.aval), "saving_bytes": 0,
                        "last_use_source": None})
            continue
        if is_donated or id(v) in outset:
            continue                     # donated already / returned
        b = aval_bytes(v.aval)
        if b < min_bytes:
            continue
        candidates.append((b, k, v))
    candidates.sort(key=lambda t: -t[0])
    for b, k, v in candidates[:max(0, max_candidates)]:
        trial = list(donated) + [False] * (len(jaxpr.invars) - len(donated))
        trial[k] = True
        saving = base.static_peak_bytes - \
            jaxpr_liveness(closed, trial, top_k=0).static_peak_bytes
        if saving <= 0:
            continue
        lu = last_use.get(v)
        src = eqn_source(jaxpr.eqns[lu]) if lu is not None else None
        out.append({"kind": "miss", "argnum": k, "bytes": b,
                    "saving_bytes": int(saving), "last_use_source": src})
    return out


def crosscheck(static_peak_bytes: Optional[int],
               argument_bytes: Optional[int],
               output_bytes: Optional[int],
               temp_bytes: Optional[int],
               alias_bytes: Optional[int] = None,
               rtol: float = CROSSCHECK_RTOL) -> Optional[dict]:
    """Compare the static estimate against XLA ``memory_analysis()``
    figures. Returns ``None`` when the backend reported nothing (the
    honesty contract: no fake numbers) — otherwise a dict with the XLA
    resident footprint (argument + temp + output, donated aliases
    counted once), the ratio, and whether it sits inside the documented
    ``CROSSCHECK_RTOL`` bracket."""
    if static_peak_bytes is None or temp_bytes is None \
            or output_bytes is None:
        return None
    xla = int(temp_bytes) + int(output_bytes) + int(argument_bytes or 0) \
        - int(alias_bytes or 0)
    if xla <= 0 or static_peak_bytes <= 0:
        return None
    ratio = float(static_peak_bytes) / float(xla)
    return {"xla_bytes": xla, "static_peak_bytes": int(static_peak_bytes),
            "ratio": ratio, "rtol": rtol,
            "ok": (1.0 / rtol) <= ratio <= rtol}
