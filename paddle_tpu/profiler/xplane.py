"""XPlane (.xplane.pb) parser + device-op statistics.

Reference analog: the profiler_statistic.py device-time tables built from
the C++ HostTraceAnalyzer/ChromeTracingLogger stack
(python/paddle/profiler/profiler_statistic.py). TPU-native: the device
timeline comes out of PjRt/XLA as an XPlane protobuf written by
``jax.profiler.start_trace``; this module decodes it with a small
wire-format reader (no tensorflow/tensorboard dependency in the image)
and aggregates device time per op and per SECTION of the program.

XPlane schema (tensorflow/core/profiler/protobuf/xplane.proto):
XSpace.planes[].lines[].events[] with event durations in picoseconds and
names interned in plane-level event_metadata; stats (on an event, or on
an event's metadata) name their key through plane-level stat_metadata.

What the stats hold on a TPU's device plane, and who reads them here:

* the METADATA of every XLA op carries ``tf_op`` (the ``jax.named_scope``
  path the op was traced under, ``jit(fused_step_q512_t64)/layer3/qkv/
  dot_general:``), ``source`` (file:line), ``flops``, ``bytes_accessed``
  and ``program_id`` (the fingerprint in its module's name):
  :func:`op_metadata`, the ``section`` / ``module`` / ``source`` of
  :func:`device_op_table`'s rows, :func:`device_section_table`;
* the ``XLA Modules`` line has one event a program execution, named
  ``jit_<function>(<fingerprint>)``, with the per-event stat ``run_id``;
  the host plane's ``DoEnqueueProgram`` events carry the same ``run_id``:
  :func:`module_events`, :func:`enqueue_events` — a launch on the host
  joined to its execution on the device.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
import struct
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

__all__ = ["parse_xspace", "device_op_table", "device_events",
           "latest_xplane_file", "summary_table", "op_metadata",
           "section_of", "module_events", "enqueue_events",
           "device_section_table"]

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
NO_SECTION = "(no scope)"


# ---------------------------------------------------------------------------
# minimal protobuf wire-format reader
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = 0
    shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """Yield (field_number, wire_type, value). Length-delimited values are
    bytes; varints are ints; fixed32/64 are raw ints."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:  # varint
            val, i = _varint(buf, i)
        elif wire == 1:  # fixed64
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 2:  # length-delimited
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wire == 5:  # fixed32
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


def _parse_stat(buf: bytes) -> Tuple[int, object]:
    """XStat -> (stat metadata id, value): a double, an int, a str, bytes,
    or ``("ref", id)`` for a string interned in the stat metadata."""
    mid, val = 0, None
    for field, _, v in _fields(buf):
        if field == 1:
            mid = v
        elif field == 2:
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif field in (3, 4, 6):          # uint64, int64, bytes
            val = v
        elif field == 5:
            val = v.decode("utf-8", "replace")
        elif field == 7:
            val = ("ref", v)
    return mid, val


def _stats(raw: Sequence[bytes], names: Dict[int, str]) -> Dict[str, object]:
    """Raw XStat messages -> {stat name: value}."""
    out = {}
    for buf in raw:
        mid, val = _parse_stat(buf)
        if isinstance(val, tuple):
            val = names.get(val[1], "")
        out[names.get(mid, f"#{mid}")] = val
    return out


def _parse_event(buf: bytes) -> Tuple[int, int, int, Tuple[bytes, ...]]:
    """XEvent -> (metadata_id, offset_ps, duration_ps, raw stats): the
    stats stay undecoded (:func:`_stats`) — a trace holds a million
    events and a reader wants the stats of a few lines."""
    meta, off, dur, raw = 0, 0, 0, ()
    for field, _, val in _fields(buf):
        if field == 1:
            meta = val
        elif field == 2:
            off = val
        elif field == 3:
            dur = val
        elif field == 4:
            raw += (val,)
    return meta, off, dur, raw


def _parse_line(buf: bytes) -> Tuple[str, int, list]:
    """XLine -> (name, timestamp_ns, [(metadata_id, offset_ps,
    duration_ps, raw stats)]). ``timestamp_ns`` is the line's epoch on
    the producer's clock; event offsets are relative to it — the unified
    timeline merger needs both to place device ops on the host axis."""
    name = ""
    ts_ns = 0
    events = []
    for field, _, val in _fields(buf):
        if field == 2:
            name = val.decode("utf-8", "replace")
        elif field == 3:
            ts_ns = val
        elif field == 4:
            events.append(_parse_event(val))
    return name, ts_ns, events


def _parse_event_metadata(buf: bytes) -> Tuple[int, str, Tuple[bytes, ...]]:
    """map entry -> XEventMetadata -> (id, name, raw stats)."""
    mid, name, raw = 0, "", ()
    for field, _, val in _fields(buf):
        if field == 1:  # map key
            mid = val
        elif field == 2:  # map value: XEventMetadata
            for f2, _, v2 in _fields(val):
                if f2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif f2 == 4 and not name:
                    name = v2.decode("utf-8", "replace")
                elif f2 == 5:
                    raw += (v2,)
    return mid, name, raw


def _parse_stat_metadata(buf: bytes) -> Tuple[int, str]:
    """map entry -> XStatMetadata -> (id, name)."""
    mid, name = 0, ""
    for field, _, val in _fields(buf):
        if field == 1:
            mid = val
        elif field == 2:
            for f2, _, v2 in _fields(val):
                if f2 == 2:
                    name = v2.decode("utf-8", "replace")
    return mid, name


def _parse_plane(buf: bytes, with_lines: bool = True) -> dict:
    name = ""
    lines = []
    meta: Dict[int, str] = {}
    raw_stats: Dict[int, Tuple[bytes, ...]] = {}
    stat_names: Dict[int, str] = {}
    for field, _, val in _fields(buf):
        if field == 2:
            name = val.decode("utf-8", "replace")
        elif field == 3:
            if with_lines:
                lines.append(_parse_line(val))
        elif field == 4:
            mid, mname, raw = _parse_event_metadata(val)
            meta[mid] = mname
            if raw:
                raw_stats[mid] = raw
        elif field == 5:
            sid, sname = _parse_stat_metadata(val)
            stat_names[sid] = sname
    return {"name": name, "lines": lines, "event_metadata": meta,
            "event_stats": {mid: _stats(raw, stat_names)
                            for mid, raw in raw_stats.items()},
            "stat_metadata": stat_names}


def parse_xspace(data: bytes, with_lines: bool = True) -> List[dict]:
    """XSpace bytes -> [{name, lines: [(line_name, timestamp_ns,
    [(meta_id, offset_ps, dur_ps, raw stats)])], event_metadata: {id:
    name}, event_stats: {id: {stat: value}} (the stats an event's
    METADATA carries), stat_metadata: {id: name}}]. ``with_lines=False``
    skips the events: the metadata tables alone are a few thousand
    entries of a file that may hold a million events."""
    return [_parse_plane(val, with_lines) for field, _, val in _fields(data)
            if field == 1]


def _read(path: str, with_lines: bool = True) -> List[dict]:
    with open(path, "rb") as f:
        return parse_xspace(f.read(), with_lines)


def _is_device_line(plane_name: str, line_name: str) -> bool:
    """Version-tolerant "is this a device/executable timeline" test.

    On TPU the device ops live in ``/device:TPU:*`` planes. On the CPU
    backend they live in the host plane, in the XLA client's line —
    whose NAME drifts with the jax/xla version: ``XLAPjRt...`` on
    older stacks, ``tf_XLATfrtCpuClient/<id>`` on the 0.4.37 image
    (the drift that emptied ``device_op_table`` here). Match the
    stable substring — an XLA-client marker — rather than any one
    release's spelling."""
    if ("/device:" in plane_name or "TPU" in plane_name
            or "GPU" in plane_name):
        return True
    return "XLA" in line_name


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def latest_xplane_file(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _latest_planes(trace_dir: str) -> List[dict]:
    """The parsed planes of the newest xplane.pb under ``trace_dir``
    (none where there is no such file)."""
    path = latest_xplane_file(trace_dir)
    return _read(path) if path else []


# parts of a scope path that are no scope a program named: transform and
# call wrappers (``jit(f)``, ``jvp(...)``), control flow, kernel calls, an
# einsum's spec
_WRAPPER = re.compile(r"^[\w.<>]+\(.*\)$")
_CONTROL = re.compile(r"^(while|body|cond|branch_\d+_fun|closed_call|"
                      r"checkpoint|remat|pallas_call|custom_[jv][vj]p_call|"
                      r"[\w,.]+->[\w.]*)$")


def section_of(tf_op: str) -> str:
    """The section of the program an op traced under the scope path
    ``tf_op`` belongs to: the innermost ``jax.named_scope`` of the
    program's own function (of a serving step a word of
    ``models/decoder_spec.py`` ``SECTIONS``) -- what a call inside a
    scope names (``norm/jit(raw)/fused_layer_norm_fwd``) is the
    scope's; ``NO_SECTION`` where the path holds none."""
    found = NO_SECTION
    for part in tf_op.rstrip(":").split("/")[:-1]:
        if _WRAPPER.match(part):
            if found != NO_SECTION:
                break
        elif part and not _CONTROL.match(part):
            found = part
    return found


def op_metadata(path: str) -> Dict[str, List[dict]]:
    """{device plane name: [{"id", "name", <every stat of the entry>}]}
    of the file's event-metadata tables, the events themselves not
    read: which scope path (``tf_op``), source line (``source``), work
    (``flops``, ``bytes_accessed``) and program (``program_id``) the
    plane's op names stand for."""
    return {
        plane["name"]: [{"id": mid, "name": name,
                         **plane["event_stats"].get(mid, {})}
                        for mid, name in plane["event_metadata"].items()]
        for plane in _read(path, with_lines=False)
        if "/device:" in plane["name"]}


def _line_events(plane: dict, line_name: str) -> List[tuple]:
    """(start_ps, end_ps, metadata_id, raw stats) of the plane's events
    on the lines called ``line_name``, on the producer's clock."""
    out = []
    for name, ts_ns, events in plane["lines"]:
        if name == line_name:
            t0 = ts_ns * 1000
            out += [(t0 + off, t0 + off + dur, mid, raw)
                    for mid, off, dur, raw in events]
    return out


def _program_id(module_name: str) -> Optional[int]:
    m = re.search(r"\((\d+)\)$", module_name)
    return int(m.group(1)) if m else None


def enqueue_events(planes: List[dict]) -> Dict[int, float]:
    """{run_id: start_us} of the host planes' ``DoEnqueueProgram``
    events: when the runtime handed each program execution to the
    device."""
    out = {}
    for plane in planes:
        if plane["name"].startswith("/host:"):
            names = plane["stat_metadata"]
            wanted = {mid for mid, name in plane["event_metadata"].items()
                      if name == "DoEnqueueProgram"}
            for line_name, ts_ns, events in plane["lines"]:
                for mid, off, _dur, raw in events:
                    if mid in wanted:
                        run_id = _stats(raw, names).get("run_id")
                        if run_id is not None:
                            out[int(run_id)] = ts_ns / 1e3 + off / 1e6
    return out


def module_events(trace_dir: str) -> List[dict]:
    """One row a program execution on a device, oldest first: ``{plane,
    module`` (``jit_<function>``), ``program_id, run_id, t_us, dur_us,
    enqueue_us}`` from the newest xplane.pb's ``XLA Modules`` lines.
    ``enqueue_us`` is the start of the host's ``DoEnqueueProgram`` event
    with the same ``run_id`` (None where the trace holds none), on the
    HOST plane's clock, which need not be the device plane's to the
    millisecond: the launch as the host dispatched it, joined to its run
    on the device by the id, not by the time."""
    return _module_rows(_latest_planes(trace_dir))


def _module_rows(planes: List[dict]) -> List[dict]:
    enqueued = enqueue_events(planes)
    rows = []
    for plane in planes:
        if "/device:" not in plane["name"]:
            continue
        names = plane["stat_metadata"]
        for start, end, mid, raw in _line_events(plane, MODULES_LINE):
            name = plane["event_metadata"].get(mid, f"#{mid}")
            run_id = _stats(raw, names).get("run_id")
            rows.append({
                "plane": plane["name"], "module": name.split("(")[0],
                "program_id": _program_id(name), "run_id": run_id,
                "t_us": start / 1e6, "dur_us": (end - start) / 1e6,
                "enqueue_us": enqueued.get(run_id)})
    rows.sort(key=lambda r: r["t_us"])
    return rows


def _own_ps(events: List[tuple]) -> Dict[int, List[int]]:
    """{metadata id: [own ps, calls]} of possibly nested events of one
    line: an op's own time is its duration less what the ops nested in
    it (a ``while``'s body) cover, so the own times add up to the
    line's busy time."""
    total: Dict[int, List[int]] = {}
    stack: List[list] = []          # [end, metadata id, own ps]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, mid, own = stack.pop()
            cell = total.setdefault(mid, [0, 0])
            cell[0] += own
            cell[1] += 1

    for start, end, mid, _ in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, mid, end - start])
    close(float("inf"))
    return total


def device_section_table(trace_dir: str) -> List[dict]:
    """Device time by section of the program, per module: rows ``{plane,
    module, section, calls, total_us, share}`` sorted by time, from the
    newest xplane.pb's ``XLA Ops`` lines. An op's section is
    :func:`section_of` its ``tf_op``, its module the one whose
    fingerprint is its ``program_id``; times are OWN times, so a plane's
    shares add up to 1 of its busy time. Empty where the trace holds no
    such line (the CPU backend's)."""
    return _section_rows(_latest_planes(trace_dir))


def _section_rows(planes: List[dict]) -> List[dict]:
    rows = []
    for plane in planes:
        modules = {_program_id(plane["event_metadata"].get(mid, "")):
                   plane["event_metadata"][mid].split("(")[0]
                   for _, _, mid, _ in _line_events(plane, MODULES_LINE)}
        own = _own_ps(_line_events(plane, OPS_LINE))
        busy = sum(ps for ps, _ in own.values())
        agg: Dict[Tuple[str, str], List[int]] = {}
        for mid, (ps, calls) in own.items():
            stats = plane["event_stats"].get(mid, {})
            key = (modules.get(stats.get("program_id"), "(no module)"),
                   section_of(str(stats.get("tf_op", ""))))
            cell = agg.setdefault(key, [0, 0])
            cell[0] += ps
            cell[1] += calls
        rows += [{"plane": plane["name"], "module": module,
                  "section": section, "calls": calls,
                  "total_us": ps / 1e6, "share": ps / busy if busy else 0.0}
                 for (module, section), (ps, calls) in agg.items()]
    rows.sort(key=lambda r: -r["total_us"])
    return rows


def device_op_table(trace_dir: str, device_only: bool = True
                    ) -> List[dict]:
    """Aggregate per-op device time from the newest xplane.pb under
    ``trace_dir``. Returns rows sorted by total time:
    {name, plane, calls, total_us, avg_us, section, source}: ``section``
    is :func:`section_of` the op's ``tf_op`` and ``source`` the
    file:line that made it, where the op's metadata says (a TPU's)."""
    return _op_rows(_latest_planes(trace_dir), device_only)


def _op_rows(all_planes: List[dict], device_only: bool) -> List[dict]:
    agg: Dict[Tuple[int, str], List[float]] = {}
    planes = {}
    for plane in all_planes:
        pname = plane["name"]
        planes[pname] = plane
        for line_name, _ts_ns, events in plane["lines"]:
            if device_only and not _is_device_line(pname, line_name):
                continue
            for mid, _off_ps, dur_ps, _ in events:
                cell = agg.setdefault((mid, pname), [0.0, 0])
                cell[0] += dur_ps / 1e6  # ps -> us
                cell[1] += 1
    rows = []
    for (mid, pname), (tot, cnt) in agg.items():
        stats = planes[pname]["event_stats"].get(mid, {})
        rows.append({
            "name": planes[pname]["event_metadata"].get(mid, f"#{mid}"),
            "plane": pname, "calls": cnt, "total_us": tot,
            "avg_us": tot / cnt,
            "section": section_of(str(stats["tf_op"]))
            if "tf_op" in stats else None,
            "source": stats.get("source")})
    rows.sort(key=lambda r: -r["total_us"])
    return rows


def device_events(trace_dir: str, device_only: bool = True) -> List[dict]:
    """Individual timed device events from the newest xplane.pb:
    ``{name, plane, line, t_us, dur_us}`` with ``t_us`` on the
    PRODUCER's clock (``line.timestamp_ns + event.offset_ps``) — the
    unified-timeline merger (:mod:`.timeline`) shifts them onto the
    host ``perf_counter`` axis. Zero-duration bookkeeping events are
    dropped."""
    path = latest_xplane_file(trace_dir)
    if path is None:
        return []
    rows = []
    for plane in _read(path):
        pname = plane["name"]
        meta = plane["event_metadata"]
        for line_name, ts_ns, events in plane["lines"]:
            if device_only and not _is_device_line(pname, line_name):
                continue
            for mid, off_ps, dur_ps, _ in events:
                if dur_ps <= 0:
                    continue
                rows.append({
                    "name": meta.get(mid, f"#{mid}"),
                    "plane": pname, "line": line_name,
                    "t_us": ts_ns / 1e3 + off_ps / 1e6,
                    "dur_us": dur_ps / 1e6,
                })
    rows.sort(key=lambda r: r["t_us"])
    return rows


def summary_table(trace_dir: str, limit: int = 30,
                  device_only: bool = True) -> str:
    """Formatted device view (≙ profiler_statistic.py's): time per op,
    then — where the trace is a TPU's — per launched program (how many
    launches, how long on the device) and per section of each program."""
    planes = _latest_planes(trace_dir)       # one parse for the three views
    rows = _op_rows(planes, device_only)
    if not rows:
        return "(no xplane trace found under %s)" % trace_dir
    lines = [f"{'Device op':<48} {'Calls':>7} {'Total(us)':>12} "
             f"{'Avg(us)':>10}"]
    for r in rows[:limit]:
        lines.append(f"{r['name'][:48]:<48} {r['calls']:>7} "
                     f"{r['total_us']:>12.1f} {r['avg_us']:>10.1f}")
    if len(rows) > limit:
        lines.append(f"... ({len(rows) - limit} more rows)")
    launches: Dict[str, List[dict]] = {}
    for m in _module_rows(planes):
        launches.setdefault(m["module"], []).append(m)
    if launches:
        lines += ["", f"{'Program (XLA Modules)':<48} {'Launches':>8} "
                      f"{'Total(ms)':>11} {'Median(ms)':>11}"]
        for name, ms in sorted(launches.items(),
                               key=lambda kv: -sum(m["dur_us"]
                                                   for m in kv[1])):
            durs = [m["dur_us"] for m in ms]
            lines.append(f"{name[:48]:<48} {len(ms):>8} "
                         f"{sum(durs) / 1e3:>11.3f} "
                         f"{statistics.median(durs) / 1e3:>11.3f}")
    sections = _section_rows(planes)
    if sections:
        lines += ["", f"{'Program':<34} {'Section':<16} {'Ops':>7} "
                      f"{'Own(ms)':>10} {'Share':>7}"]
        for r in sections[:limit]:
            lines.append(f"{r['module'][:34]:<34} {r['section'][:16]:<16} "
                         f"{r['calls']:>7} {r['total_us'] / 1e3:>10.3f} "
                         f"{100 * r['share']:>6.1f}%")
        if len(sections) > limit:
            lines.append(f"... ({len(sections) - limit} more rows)")
    return "\n".join(lines)
