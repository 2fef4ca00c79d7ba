"""The program's own spans, read from the trace the device ops are in.

The program marks its scheduler cycle (``serving/cycle`` and its children
``sweep``, ``admit``, ``plan``, ``decode_dispatch``, ``host_fetch``,
``emit``, ``record``, and ``serving/wait`` between two cycles; each
carries ``cycle=n``) and its train step
(``hapi/train_batch`` with ``step=n``, ``hapi/host_sync``) as
``jax.profiler.TraceAnnotation``s. While the benchmark's trace slice runs
they land on the ``/host:CPU`` plane of the same ``.xplane.pb``, one line
per thread, on the clock of ``trace_reduce``'s device intervals, with the
keyword arguments as event stats. The file is opened once a run, and the
same pass keeps the device intervals of the ragged attention kernel, which
``trace_reduce`` only sums. A program that marks nothing (an older
commit) leaves nothing here: every reader then finds no span and returns
``None``.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from . import harness as H
from . import stats as S
from . import trace_reduce as TR

HOST_PLANE = "/host:CPU"
PREFIXES = ("serving/", "hapi/")
CYCLE = "serving/cycle"
NESTED = (CYCLE, "serving/prefill")     # hold, or lie inside, other spans
KERNEL = "ragged_paged_attention"
NO_SPAN = "no span"

Span = Tuple[int, int, str, dict]       # (start_ns, end_ns, name, args)


def read_trace(path: str) -> Tuple[List[Span], List[Tuple[int, int]]]:
    """One pass over the file: every ``/host:CPU`` event whose name starts
    with ``serving/`` or ``hapi/``, oldest first, and the merged intervals
    of the first device plane's ops named after ``KERNEL``."""
    from jax.profiler import ProfileData
    spans, kernel = [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(PREFIXES):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), name,
                                      dict(ev.stats)))
        elif plane.name.startswith(TR.DEVICE_PREFIX) and kernel is None:
            lines = list(plane.lines)
            kernel = [(int(ev.start_ns),
                       int(ev.start_ns) + int(ev.duration_ns))
                      for ln in ([ln for ln in lines
                                  if ln.name == TR.OPS_LINE] or lines)
                      for ev in ln.events
                      if KERNEL in TR.short_name(ev.name)]
    spans.sort(key=lambda sp: (sp[0], -sp[1]))
    return spans, TR._merge(kernel or [])


def host_spans(readings: dict) -> List[Span]:
    """The spans of the run's trace slice, read once and kept in
    ``readings["host_spans"]`` (the kernel's intervals beside them in
    ``readings["kernel_intervals"]``). The serving drivers name the
    slice's directory; the training driver traces into
    ``out/trace-train`` and says only that it traced. No trace, or no
    file: no spans."""
    if "host_spans" not in readings:
        if "slice" in readings:
            trace_dir = readings["slice"]["dir"]
        elif "trace_steps" in readings:
            trace_dir = H.out_path("trace-train")
        else:
            trace_dir = None
        path = TR.latest_xplane(trace_dir) if trace_dir else None
        readings["host_spans"], readings["kernel_intervals"] = \
            read_trace(path) if path else ([], [])
    return readings["host_spans"]


def median_ms(readings: dict, name: str) -> Optional[float]:
    """Median length in ms of the spans called ``name``."""
    lengths = [(e - s) / 1e6 for s, e, n, _ in host_spans(readings)
               if n == name]
    return S.median(lengths) if lengths else None


def by_cycle(readings: dict, name: str) -> Dict[int, Tuple[int, int]]:
    """{cycle number: (start_ns, end_ns)} of the spans called ``name``."""
    return {int(a["cycle"]): (s, e) for s, e, n, a in host_spans(readings)
            if n == name and "cycle" in a}


def slice_records(readings: dict) -> List[dict]:
    """The flight recorder's records of the cycles that lie whole inside
    the trace slice: those whose ``serving/cycle`` span is in it."""
    whole = by_cycle(readings, CYCLE)
    return [c for c in readings.get("cycles", []) if c["cycle"] in whole]


def row_fill(readings: dict, chunk: bool) -> Optional[float]:
    """Real query rows over the rows the launched programs compute, %:
    sum of ``launch_rows`` over sum of ``launch_q`` of the slice's cycles
    that carried a prompt chunk (``chunk``) or decode rows only."""
    counted = [c for c in slice_records(readings) if c.get("launch_q")
               and (c.get("chunk_tokens", 0) > 0) == chunk]
    if not counted:
        return None
    return 100.0 * sum(c["launch_rows"] for c in counted) \
        / sum(c["launch_q"] for c in counted)


def busy_ns(intervals: List[Tuple[int, int]], lo: int, hi: int) -> int:
    """Device-busy time inside [lo, hi) given merged busy intervals."""
    first = bisect.bisect_right(intervals, lo, key=lambda iv: iv[0]) - 1
    total = 0
    for s, e in intervals[max(0, first):]:
        if s >= hi:
            break
        total += max(0, min(e, hi) - max(s, lo))
    return total


def launch_windows(readings: dict) -> Dict[int, Tuple[int, int]]:
    """{cycle: (decode_dispatch start, host_fetch end)}: the stretch of
    the host's cycle inside which that cycle's launch runs on the
    device."""
    launch = by_cycle(readings, "serving/decode_dispatch")
    fetch = by_cycle(readings, "serving/host_fetch")
    return {n: (launch[n][0], fetch[n][1]) for n in sorted(launch)
            if n in fetch}


def launches(readings: dict) -> List[Tuple[dict, int, int]]:
    """(the cycle's record, start_ns, end_ns of its launch's stretch) of
    the slice's launches whose record the poll caught."""
    records = {c["cycle"]: c for c in readings.get("cycles", [])}
    return [(records[n], lo, hi)
            for n, (lo, hi) in launch_windows(readings).items()
            if n in records]


def launch_busy_ms(readings: dict, chunk: bool) -> List[Tuple[dict, float]]:
    """(record, device-busy ms) of each of the slice's launches that
    carried a prompt chunk (``chunk``) or decode rows only."""
    if "trace" not in readings:
        return []
    intervals = readings["trace"]["intervals"]
    return [(rec, busy_ns(intervals, lo, hi) / 1e6)
            for rec, lo, hi in launches(readings)
            if (rec.get("chunk_tokens", 0) > 0) == chunk]


def decode_step_ms(readings: dict) -> Optional[float]:
    """Median device-busy ms of one launch of decode rows only."""
    times = [ms for _, ms in launch_busy_ms(readings, chunk=False)]
    return S.median(times) if times else None


def chunk_step_us(readings: dict, heads_layers: int) -> Optional[dict]:
    """Device-busy us per kernel step (one q block against one KV block,
    in one head of one layer: the record's ``kv_steps`` times
    ``heads_layers``): ``plain`` over the slice's launches of decode rows
    only, ``chunk`` over what the launches with a prompt chunk took above
    the plain launch, by the steps they walked above it. A chunk's q
    blocks each walk its whole context, so its cost goes with the square
    of its size: neither the whole launch's time (688 ms at 173 tokens,
    1,306 at 456) nor the time a chunk token (0.67 ms, 1.28 ms) holds
    from one 11-cycle slice to the next; the time a step does."""
    plain = [(rec["kv_steps"], ms)
             for rec, ms in launch_busy_ms(readings, chunk=False)
             if rec.get("kv_steps")]
    chunks = [(rec["kv_steps"], ms)
              for rec, ms in launch_busy_ms(readings, chunk=True)
              if rec.get("kv_steps")]
    if not plain or not chunks:
        return None
    steps, ms = (S.median([p[i] for p in plain]) for i in (0, 1))
    extra = sum(n - steps for n, _ in chunks)
    if extra <= 0:
        return None
    return {"plain": 1e3 * ms / (steps * heads_layers),
            "chunk": 1e3 * sum(t - ms for _, t in chunks)
            / (extra * heads_layers)}


def kv_read_gbs(readings: dict, bytes_per_token: int) -> Optional[float]:
    """GB/s at which the ragged kernel gets through the context it must
    read: the launches' ``kv_tokens`` times a token's K and V bytes over
    all layers, over the kernel's device time inside those launches'
    stretches."""
    host_spans(readings)
    kernel = readings.get("kernel_intervals")
    counted = [(rec["kv_tokens"], lo, hi)
               for rec, lo, hi in launches(readings) if rec.get("kv_tokens")]
    ns = sum(busy_ns(kernel, lo, hi) for _, lo, hi in counted) \
        if kernel else 0
    if not ns:
        return None
    return sum(t for t, _, _ in counted) * bytes_per_token / ns


def clock_check(readings: dict) -> Optional[dict]:
    """Do the host spans and the device ops share a clock? Between one
    cycle's ``host_fetch`` end and the next cycle's ``decode_dispatch``
    start the host has fetched everything it launched, so a device
    interval that reaches into such a stretch is a launch that started
    before its dispatch span or ended after its fetch span: a violation.
    Also how long after a dispatch span's start its first device op
    starts, and how long before a fetch span's end the last one ends."""
    windows = launch_windows(readings)
    if not windows or "trace" not in readings:
        return None
    intervals = readings["trace"]["intervals"]
    stretches = [(windows[n][1], windows[n + 1][0]) for n in windows
                 if n + 1 in windows]
    violations = sum(1 for lo, hi in stretches if busy_ns(intervals, lo, hi))
    starts = [s for s, _ in intervals]
    lead, lag = [], []
    for lo, hi in windows.values():
        i = bisect.bisect_left(starts, lo)
        j = bisect.bisect_left(starts, hi) - 1
        if i <= j:
            lead.append((intervals[i][0] - lo) / 1e6)
            lag.append((hi - intervals[j][1]) / 1e6)
    return {"cycles": len(windows), "stretches": len(stretches),
            "violations": violations,
            "launch_lead_ms": S.median(lead) if lead else None,
            "fetch_lag_ms": S.median(lag) if lag else None}


def idle_by_span(readings: dict) -> Optional[Dict[str, int]]:
    """Device idle ns (the gaps between busy intervals) under each span
    name of the scheduler thread, and under ``NO_SPAN``: a stretch of a
    cycle that no child span covers. By overlap, not by a gap's midpoint:
    a serial cycle leaves ONE long gap a launch, from the last device op
    to the next launch's first, and it crosses every host phase in
    between. Gaps are cut to the stretch from the first recorded span to
    the last: a span that began before the trace did is not in it, so
    what lies before says nothing about the spans."""
    by_name: Dict[str, list] = {}
    for s, e, n, _ in host_spans(readings):
        if n.startswith("serving/") and n not in NESTED:
            by_name.setdefault(n, []).append((s, e))
    if not by_name or "trace" not in readings:
        return None
    first = min(iv[0][0] for iv in by_name.values())
    last = max(e for iv in by_name.values() for _, e in iv)
    gaps = [(max(s, first), min(e, last))
            for s, e in TR.gaps(readings["trace"])
            if s < last and e > first]
    total = {n: sum(busy_ns(TR._merge(iv), lo, hi) for lo, hi in gaps)
             for n, iv in by_name.items()}
    total[NO_SPAN] = sum(e - s for s, e in gaps) - sum(total.values())
    return total


def unplaced_idle_share(readings: dict) -> Optional[float]:
    """Share (%) of the slice's device idle time that lies under no span
    of the scheduler thread."""
    idle = idle_by_span(readings)
    if not idle or not sum(idle.values()):
        return None
    return 100.0 * idle[NO_SPAN] / sum(idle.values())
