"""From a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and the longest device gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane
is one whose name starts with ``/device:TPU:``; its ``XLA Ops`` line
holds one event per executed operation (nested where an op such as a
``while`` encloses others). Busy time is the union of those intervals;
an op's own time is its duration less what its nested children cover.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def latest_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(events: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Per-name own time (ns) of possibly nested events of ONE line."""
    total: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, own_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            total[name] = total.get(name, 0.0) + own

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return total


def short_name(hlo: str) -> str:
    """A readable, stable label for a trace event named by its HLO text:
    the instruction's name without ``%`` and its numeric suffix (so the
    same op of every layer sums up), and for XLA's own fusions — whose
    names say nothing — the result's type. A kernel keeps the ``name=``
    its author gave it."""
    head, sep, rest = hlo.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.lstrip("%").strip())
    if not sep:
        return base or hlo[:80]
    if base.endswith("fusion") or base in ("copy", "bitcast", "convert"):
        kind = re.match(r"\(?\s*([a-z0-9]+\[[\d,]*\])", rest)
        if kind:
            base = f"{base} {kind.group(1)}"
    return base[:80]


def device_planes(path: str) -> Dict[str, List[Tuple[int, int, str]]]:
    """{device plane name: [(start_ns, end_ns, op name)]} of the
    ``XLA Ops`` line (every line of the plane if it has none)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = list(plane.lines)
        chosen = [ln for ln in lines if ln.name == OPS_LINE] or lines
        events = []
        for ln in chosen:
            for ev in ln.events:
                s = int(ev.start_ns)
                events.append((s, s + int(ev.duration_ns), short_name(ev.name)))
        out[plane.name] = events
    return out


def reduce_events(planes: Dict[str, List[Tuple[int, int, str]]],
                  window_s: float) -> dict:
    """Busy seconds (averaged over the device planes), per-op own
    seconds (summed over planes, then averaged) and the merged busy
    intervals of the first plane."""
    if not planes:
        raise ValueError("the trace holds no device plane: nothing ran on "
                         "the device inside the traced window")
    busy, ops = [], {}
    merged_first = None
    for name in sorted(planes):
        events = planes[name]
        merged = _merge([(s, e) for s, e, _ in events])
        if merged_first is None:
            merged_first = merged
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for op, ns in _self_times(events).items():
            ops[op] = ops.get(op, 0.0) + ns / 1e9
    n = len(planes)
    return {"busy_s": sum(busy) / n, "window_s": float(window_s),
            "ops": {k: v / n for k, v in ops.items()},
            "intervals": merged_first, "planes": n}


def reduce_trace(path: str, window_s: float) -> dict:
    return reduce_events(device_planes(path), window_s)


def op_seconds(reduced: dict, *needles: str) -> float:
    """Own device seconds of every op whose name contains a needle."""
    return sum(v for k, v in reduced["ops"].items()
               if any(n in k for n in needles))


def top_ops(reduced: dict, k: int = 10) -> list:
    return [[name, secs] for name, secs in
            sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:k]]


def gaps(reduced: dict) -> List[Tuple[int, int]]:
    """(start_ns, end_ns) of every device gap between busy intervals."""
    iv = reduced["intervals"]
    return [(iv[i][1], iv[i + 1][0]) for i in range(len(iv) - 1)
            if iv[i + 1][0] > iv[i][1]]


def label_gaps(gap_list: List[Tuple[int, int]], spans: List[Tuple[int, int, str]],
               other: str, k: int = 10) -> list:
    """Sum gap time by what the host was doing: each gap goes to the
    span (start_ns, end_ns, label) that holds its midpoint, else to
    ``other``. Returns the ``k`` largest ``[label, seconds]``."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    import bisect
    total: Dict[str, float] = {}
    for s, e in gap_list:
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        label = other
        if i >= 0 and spans[i][0] <= mid < spans[i][1]:
            label = spans[i][2]
        total[label] = total.get(label, 0.0) + (e - s) / 1e9
    return [[name, secs] for name, secs in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]
