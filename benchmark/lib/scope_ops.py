"""Device time by the program's ``jax.named_scope``.

A device trace names an op by its optimized HLO instruction (``%fusion.7
= bf16[...] fusion(...)``), and the scope a jax op was traced under
survives only in the compiled module's text, as ``metadata={op_name=
".../moe_experts/..."}`` on that instruction. So the scope's time is
found in two steps: ``scope_keys`` reads the compiled text of the step
programs and keeps a key — instruction name, result type without its
layout, opcode — of
every instruction whose ``op_name`` holds the scope; ``scope_seconds``
sums the OWN time (nested ops taken out, as ``trace_reduce`` does) of the
trace events with such a key. A fusion carries the ``op_name`` of its
root, so an op XLA fused across the scope's edge goes to one side whole.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from . import trace_reduce as TR

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def key_of(hlo: str) -> Optional[str]:
    """``'%name = type opcode'`` of one instruction's text (a line of a
    compiled module, or a trace event's name), or None."""
    m = _INSTR.match(hlo)
    if not m:
        return None
    name, rest = m.groups()
    # the result type ends where the opcode's '(' opens at bracket depth 0
    depth, cut = 0, None
    for i, ch in enumerate(rest):
        if ch in "([{":
            if ch == "(" and depth == 0 and i and rest[i - 1] not in " ,(":
                cut = i
                break
            depth += 1
        elif ch in ")]}":
            depth -= 1
    if cut is None:
        return None
    # layouts ({1,0:T(8,128)...}) are dropped: the two texts need not
    # print them alike
    return f"{name} = {' '.join(re.sub(r'{[^}]*}', '', rest[:cut]).split())}"


def scope_keys(compiled_text: str, scope: str) -> Set[str]:
    needle = f"/{scope}/"
    keys = set()
    for line in compiled_text.splitlines():
        m = _OP_NAME.search(line)
        if m and needle in m.group(1) + "/":
            key = key_of(line)
            if key:
                keys.add(key)
    return keys


def full_name_events(path: str) -> Dict[str, List[Tuple[int, int, str]]]:
    """``trace_reduce.device_planes`` with every event keyed by
    ``key_of`` its HLO text (its short name where it has no key)."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(TR.DEVICE_PREFIX):
            continue
        lines = list(plane.lines)
        chosen = [ln for ln in lines if ln.name == TR.OPS_LINE] or lines
        out[plane.name] = [
            (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
             key_of(ev.name) or TR.short_name(ev.name))
            for ln in chosen for ev in ln.events]
    return out


def scope_seconds(planes: Dict[str, List[Tuple[int, int, str]]],
                  keys: Iterable[str], lo_ns: Optional[int] = None,
                  hi_ns: Optional[int] = None) -> float:
    """Own device seconds (averaged over the planes) of the events whose
    key is in ``keys`` and whose start lies in ``[lo_ns, hi_ns)``."""
    keys = set(keys)
    if not planes:
        return 0.0
    total = 0.0
    for events in planes.values():
        if lo_ns is not None:
            events = [ev for ev in events if lo_ns <= ev[0] < hi_ns]
        total += sum(ns for k, ns in TR._self_times(events).items()
                     if k in keys)
    return total / len(planes) / 1e9
