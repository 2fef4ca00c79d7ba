"""A launch on the device, found by its ``run_id``, and its device time
by section of the model.

Since two launches are in flight (PR 32) no stretch of the host's spans
holds one launch's device work. The trace itself says where a launch
begins and ends: the device plane's ``XLA Modules`` line has one event a
program execution, with the stat ``run_id``, and the host plane's
``DoEnqueueProgram`` events carry the same ``run_id``. The runtime
enqueues on a thread of its own, a fraction of a millisecond after the
call returned — on a small engine AFTER the ``serving/decode_dispatch
cycle=n`` span has ended — so the span is tied to its enqueue by the
runtime's own chain, not by time: the ``PJRT_LoadedExecutable_Execute
linkage`` event the dispatching thread leaves inside the span names
(``_p``) the ``PJRT_LoadedExecutable_Execute`` event (``_c``) that ran the
call, and the next enqueue after that event's start, before the next
such event's, is its program's. So launch n IS the module event whose
``run_id`` that enqueue carries (and, where the flight recorder's record
says which program it launched — ``launch_program`` — whose name is
``jit_<that>``): a join by id, no stretch, nothing counted from the
slice's edge. A launch whose dispatch span lies outside the trace, or
whose span executed no or several such programs, stays UNMATCHED and is
counted in the log line, never guessed.

Inside a matched module event every op of the ``XLA Ops`` line goes to a
SECTION (``paddle_tpu/models/decoder_spec.py`` ``SECTIONS``): the scope
path the op was traced under is the ``tf_op`` stat of its event METADATA,
which ``jax.profiler.ProfileData`` does not show; the program's own wire
reader does (``paddle_tpu.profiler.xplane.op_metadata``: the metadata
tables alone, a few thousand entries, not the events), joined to the
events by name. A kernel that loses its scope path (``ragged-dot``) is
placed by its event name; where two metadata entries share a name and
differ in section the launch's ``program_id`` decides, else the time is
UNPLACED. Times are own times (``trace_reduce._self_times``' rule: a
``while`` less its body), so a launch's sections and its unplaced time add
up to its busy time.

Everything returns ``None``, and nothing raises, on a trace or a program
that lacks what is read here (an older commit names no section).
"""
from __future__ import annotations

import bisect
import re
import time
from typing import Dict, List, Optional, Tuple

from . import harness as H
from . import host_spans as HS
from . import stats as S
from . import trace_reduce as TR

MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
EXECUTE = "PJRT_LoadedExecutable_Execute"
LINKAGE = EXECUTE + " linkage"
DISPATCH = "serving/decode_dispatch"
UNPLACED = "(unplaced)"
# kernels whose trace events carry no scope path, by a part of their name
KERNEL_SECTIONS = (("ragged-dot", "moe_experts"), ("kv_append", "cache_write"),
                   ("ragged_paged_attention", "attention"),
                   ("mla_paged_attention", "attention"))


def read_device(path: str) -> dict:
    """One pass over the file with ``ProfileData``: the first device
    plane's module events ``(start_ns, end_ns, name, run_id)`` and op
    events ``(start_ns, end_ns, name)`` (full names: the metadata's), and
    the host plane's enqueues ``(start_ns, run_id)``, linkage events
    ``(start_ns, flow id)`` and executions ``{flow id: start_ns}``."""
    from jax.profiler import ProfileData
    out = {"plane": None, "modules": [], "ops": [], "enqueues": [],
           "links": [], "executes": {}}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HS.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name == ENQUEUE:
                        run_id = dict(ev.stats).get("run_id")
                        if run_id is not None:
                            out["enqueues"].append((int(ev.start_ns),
                                                    int(run_id)))
                    elif name == LINKAGE:
                        flow = dict(ev.stats).get("_p")
                        if flow is not None:
                            out["links"].append((int(ev.start_ns), flow))
                    elif name == EXECUTE:
                        flow = dict(ev.stats).get("_c")
                        if flow is not None:
                            out["executes"][flow] = int(ev.start_ns)
        elif plane.name.startswith(TR.DEVICE_PREFIX) and out["plane"] is None:
            out["plane"] = plane.name
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        run_id = dict(ev.stats).get("run_id")
                        out["modules"].append(
                            (s, s + int(ev.duration_ns), ev.name,
                             None if run_id is None else int(run_id)))
                elif line.name == TR.OPS_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        out["ops"].append((s, s + int(ev.duration_ns),
                                           ev.name))
    for key in ("modules", "enqueues", "links"):
        out[key].sort()
    return out


def join_launches(dispatch: Dict[int, Tuple[int, int]], records: Dict[int, dict],
                  device: dict) -> Tuple[dict, dict]:
    """``({cycle: (start_ns, end_ns, module name)}, {reason: count})``:
    each dispatch span's launch by the chain linkage -> execution ->
    enqueue -> ``run_id`` -> module event (module doc) over what
    :func:`read_device` read, and why the others were not matched."""
    by_run = {run_id: (s, e, name) for s, e, name, run_id in device["modules"]
              if run_id is not None}
    enqueues, links = device["enqueues"], device["links"]
    enqueued = [s for s, _ in enqueues]
    linked = [s for s, _ in links]
    executed = sorted(device["executes"].values())

    def module_of(flow):
        """The module event of the program the execution ``flow`` ran:
        the first enqueue from its start on, before the next execution."""
        start = device["executes"].get(flow)
        if start is None:
            return None
        i = bisect.bisect_left(enqueued, start)
        j = bisect.bisect_right(executed, start)
        if i == len(enqueues) or (j < len(executed)
                                  and enqueued[i] >= executed[j]):
            return None
        return by_run.get(enqueues[i][1])

    matched, why = {}, {}

    def miss(reason):
        why[reason] = why.get(reason, 0) + 1

    for n, (lo, hi) in sorted(dispatch.items()):
        rec = records.get(n)
        if rec is None or not rec.get("launch_q"):
            miss("no launch record")
            continue
        i, j = bisect.bisect_left(linked, lo), bisect.bisect_left(linked, hi)
        found = [m for m in (module_of(flow) for _, flow in links[i:j]) if m]
        if rec.get("launch_program"):
            want = "jit_" + rec["launch_program"]
            found = [m for m in found if m[2].split("(")[0] == want]
        if len(found) == 1:
            matched[n] = found[0]
        else:
            miss("no program executed inside the dispatch span has a module "
                 "event" if not found else "several candidates")
    return matched, why


def _kernel_section(name: str) -> Optional[str]:
    head = name.split(" = ")[0]
    for needle, section in KERNEL_SECTIONS:
        if needle in head:
            return section
    return None


def _program_id(module_name: str) -> Optional[int]:
    m = re.search(r"\((\d+)\)$", module_name)
    return int(m.group(1)) if m else None


def section_table(path: str, plane: str) -> Optional[Dict[str, list]]:
    """{event name: [(section or None, program_id)]} from the plane's
    metadata table, through the program's wire reader; None where the
    program has no such reader or names no section."""
    try:
        from paddle_tpu.models import decoder_spec
        from paddle_tpu.profiler import xplane
    except ImportError:
        return None
    op_metadata = getattr(xplane, "op_metadata", None)
    section_of = getattr(decoder_spec, "section_of", None)
    if op_metadata is None or section_of is None:
        return None
    try:
        entries = op_metadata(path).get(plane, [])
    except (OSError, ValueError, IndexError) as e:   # an unreadable file
        H.log(f"launch_trace: the program's reader failed on {path}: {e!r}; "
              f"no section is read")
        return None
    table: Dict[str, list] = {}
    named = False
    for entry in entries:
        section = section_of(str(entry.get("tf_op", "")))
        named = named or section is not None
        table.setdefault(entry["name"], []).append(
            (section or _kernel_section(entry["name"]),
             entry.get("program_id")))
    return table if named else None


def place(entries: Optional[list], program_id: Optional[int]) -> Optional[str]:
    """The section of an event whose name has these metadata entries, in
    a launch of ``program_id``; None: unplaced."""
    if not entries:
        return None
    sections = {s for s, _ in entries}
    if len(sections) == 1:
        return sections.pop()
    mine = {s for s, pid in entries if pid == program_id}
    return mine.pop() if len(mine) == 1 else None


def section_times(ops: list, launches: Dict[int, tuple],
                  table: Dict[str, list]) -> Tuple[dict, dict, Dict[str, int]]:
    """``({cycle: {section: own ns}}, {cycle: busy ns}, {op name: ns}
    unplaced)`` of the ops inside the matched launches' module events."""
    order = sorted(launches)
    bounds = [(launches[n][0], launches[n][1], n) for n in order]
    bounds.sort()
    b_starts = [b[0] for b in bounds]
    keyed, inside = [], {n: [] for n in order}
    programs = {n: _program_id(launches[n][2]) for n in order}
    placed: Dict[tuple, Optional[str]] = {}     # a few thousand distinct
    for s, e, name in ops:
        i = bisect.bisect_right(b_starts, s) - 1
        if i < 0 or s >= bounds[i][1]:
            continue                      # outside every matched launch
        n = bounds[i][2]
        key = (name, programs[n])
        if key not in placed:
            placed[key] = place(table.get(name), programs[n])
        section = placed[key]
        keyed.append((s, e, (n, section or UNPLACED,
                             None if section else TR.short_name(name))))
        inside[n].append((s, e))
    by_cycle: Dict[int, Dict[str, int]] = {n: {} for n in order}
    unplaced: Dict[str, int] = {}
    for (n, section, op), ns in TR._self_times(keyed).items():
        by_cycle[n][section] = by_cycle[n].get(section, 0) + int(ns)
        if op is not None:
            unplaced[op] = unplaced.get(op, 0) + int(ns)
    busy = {n: sum(e - s for s, e in TR._merge(iv))
            for n, iv in inside.items()}
    return by_cycle, busy, unplaced


def gaps_by_span(readings: dict, gaps: List[Tuple[int, int]]) -> Dict[str, int]:
    """The gaps' ns under each ``serving/*`` span name of the scheduler
    thread, by overlap (``host_spans.idle_by_span``'s rule), the rest
    under ``host_spans.NO_SPAN``."""
    by_name: Dict[str, list] = {}
    for s, e, n, _ in HS.host_spans(readings):
        if n.startswith("serving/") and n not in HS.NESTED:
            by_name.setdefault(n, []).append((s, e))
    total = {}
    for n, iv in by_name.items():
        merged = TR._merge(iv)
        total[n] = sum(HS.busy_ns(merged, lo, hi) for lo, hi in gaps)
    total[HS.NO_SPAN] = sum(e - s for s, e in gaps) - sum(total.values())
    return total


def launch_trace(readings: dict) -> Optional[dict]:
    """The run's trace slice read once (kept in
    ``readings["launch_trace"]``): ``launches {cycle: (start_ns, end_ns,
    module name)}``, ``records {cycle: record}`` of those, ``gaps``
    [(start_ns, end_ns)] between the module events of launches n and
    n + 1 where both are matched (empty where they touch) with
    ``gap_idle``, each gap's ns in which no op ran, and — where the
    program names sections — ``sections {cycle: {section: own ns}}``,
    ``busy {cycle: ns}``. None where there is no slice, no module line or no dispatch span."""
    if "launch_trace" in readings:
        return readings["launch_trace"]
    readings["launch_trace"] = None
    if "slice" not in readings:
        return None
    path = TR.latest_xplane(readings["slice"]["dir"])
    dispatch = HS.by_cycle(readings, DISPATCH)
    if path is None or not dispatch:
        return None
    t0 = time.monotonic()
    device = read_device(path)
    if not device["modules"]:
        return None
    records = {c["cycle"]: c for c in readings.get("cycles", [])}
    launches, why = join_launches(dispatch, records, device)
    lead = [(launches[n][0] - s) / 1e6 for n, (s, _) in dispatch.items()
            if n in launches]
    H.log(f"launches by run_id: {len(launches)} of {len(dispatch)} dispatch "
          f"spans matched to a module event"
          + (f" ({100.0 * len(launches) / len(dispatch):.1f}%)")
          + (f"; not matched: {why}" if why else "")
          + f"; {len(device['modules']) - len(launches)} of "
            f"{len(device['modules'])} module events belong to no dispatch "
            f"span of the trace"
          + (f"; a module event starts {S.median(lead):.3f} ms after its "
             f"dispatch span does (median)" if lead else ""))
    if not launches:
        return None
    gaps = [(launches[n][1], max(launches[n][1], launches[n + 1][0]))
            for n in sorted(launches) if n + 1 in launches]
    # another program may run between two launches (a block copy): a gap's
    # idle time is its length less the device's busy time inside it
    merged = readings["trace"]["intervals"] if "trace" in readings \
        else TR._merge([(s, e) for s, e, _ in device["ops"]])
    out = {"launches": launches,
           "records": {n: records[n] for n in launches}, "gaps": gaps,
           "gap_idle": [hi - lo - HS.busy_ns(merged, lo, hi)
                        for lo, hi in gaps]}
    table = section_table(path, device["plane"])
    if table is not None:
        by_cycle, busy, unplaced = section_times(device["ops"], launches,
                                                 table)
        out.update(sections=by_cycle, busy=busy)
        _log_sections(out, unplaced)
    H.log(f"launch_trace: the trace read, joined and summed in "
          f"{time.monotonic() - t0:.1f} s")
    readings["launch_trace"] = out
    return out


def _log_sections(lt: dict, unplaced: Dict[str, int]) -> None:
    n = len(lt["launches"])
    total: Dict[str, int] = {}
    for sections in lt["sections"].values():
        for k, ns in sections.items():
            total[k] = total.get(k, 0) + ns
    busy = sum(lt["busy"].values())
    length = sum(e - s for s, e, _ in lt["launches"].values())
    H.log(f"device time by section, ms a launch over {n} matched launches: "
          + str({k: round(v / n / 1e6, 3) for k, v in
                 sorted(total.items(), key=lambda kv: -kv[1])}))
    H.log(f"matched module events {length / 1e9:.4f} s long, busy inside "
          f"them {busy / 1e9:.4f} s ({100.0 * busy / length:.2f}%); sections "
          f"+ unplaced {sum(total.values()) / 1e9:.4f} s")
    if unplaced:
        top = sorted(unplaced.items(), key=lambda kv: -kv[1])[:8]
        H.log("unplaced (no section in the op's metadata, no kernel name), "
              "ms a launch: "
              + str({k: round(v / n / 1e6, 3) for k, v in top}))


def launch_device_ms(readings: dict, chunk: bool) -> Optional[float]:
    """Median length in ms of the module events of the matched launches
    that carried a prompt chunk (``chunk``) or decode rows only."""
    lt = launch_trace(readings)
    if lt is None:
        return None
    ms = [(e - s) / 1e6 for n, (s, e, _) in lt["launches"].items()
          if (lt["records"][n].get("chunk_tokens", 0) > 0) == chunk]
    return S.median(ms) if ms else None


def device_gap_ms(readings: dict) -> Optional[float]:
    """Median idle ms between one launch's module event and the next
    one's; the log line splits the gaps' time by the scheduler's span
    that covered it."""
    lt = launch_trace(readings)
    if lt is None or not lt["gaps"]:
        return None
    by_span = gaps_by_span(readings, lt["gaps"])
    H.log(f"device gaps between consecutive launches: {len(lt['gaps'])} "
          f"gaps, {sum(e - s for s, e in lt['gaps']) / 1e9:.4f} s in all, "
          f"{sum(lt['gap_idle']) / 1e9:.4f} s of it idle; by the span that "
          f"covered them, s: "
          + str({k: round(v / 1e9, 4) for k, v in
                 sorted(by_span.items(), key=lambda kv: -kv[1]) if v}))
    return S.median([ns / 1e6 for ns in lt["gap_idle"]])


def section_ms(readings: dict, *sections: str) -> Optional[float]:
    """Own device ms a launch under the sections, all layers, over the
    matched launches; None where the program names none of them."""
    lt = launch_trace(readings)
    if lt is None or "sections" not in lt:
        return None
    ns = sum(by.get(s, 0) for by in lt["sections"].values()
             for s in sections)
    return ns / len(lt["launches"]) / 1e6 if ns > 0 else None


def unplaced_share(readings: dict) -> Optional[float]:
    """Busy time inside the matched launches under no section and no
    known kernel name, % of their busy time."""
    lt = launch_trace(readings)
    if lt is None or "sections" not in lt:
        return None
    busy = sum(lt["busy"].values())
    if not busy:
        return None
    return 100.0 * sum(by.get(UNPLACED, 0)
                       for by in lt["sections"].values()) / busy
