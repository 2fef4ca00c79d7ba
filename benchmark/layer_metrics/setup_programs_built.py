"""Engine and steps: programs built before the window opened: the build
events of ``engine_stats["startup"]["programs"]`` stamped before ``t0``
(every build of the engine's own jit sites keeps one: ``at``, on the
clock of ``t0``, then ``trace_ms``, ``lower_ms``, ``compile_ms``,
``cache_hits`` / ``cache_misses``, ``first_call_ms`` and the
``launch_rows`` / ``slots_active`` of the launch that asked for it). The
warm-up's programs and any the ramp built: each costs its tracing, its
lowering, its compilation or retrieval and its first call
(``setup_trace_s``, ``setup_lower_s``, ``setup_executable_s``,
``setup_first_call_s``, which read the same events through this file),
whether or not the window launches it (``launch_programs``). A site on
the plain-``jit`` fallback counts here and adds to no part. Nothing where
the program keeps no such record (every commit before PR 52)."""
from benchmark.lib import harness as H


def startup(r):
    return r.get("engine_stats", {}).get("startup")


def built_before(r):
    """The build events stamped before the window opened, oldest first;
    ``None`` where the program keeps none."""
    st = startup(r)
    if st is None:
        return None
    return [p for p in st["programs"] if p["at"] < r["t0"]]


def part_s(r, key, before=None):
    """Seconds of one part over the builds before the window (or, for the
    summary line, before another stamp)."""
    events = built_before(r)
    if events is None:
        return None
    return sum(p[key] or 0.0 for p in events if not p.get("fallback")
               and (before is None or p["at"] < before)) / 1e3


def read(r):
    events = built_before(r)
    if events is None:
        return None
    st = startup(r)
    # the summary: the parts in time order. A program the ramp built lies
    # inside the ramp, so the remainder (the warm-up waves RUNNING their
    # launches) is taken with the builds before the first request alone
    first = r.get("door_stats", {}).get("first_request_t")
    parts = {"before engine": st["t_build"] - (r["t0"] - r["setup_s"]),
             "engine build": st["build_ms"] / 1e3,
             **{name: part_s(r, name + "_ms", before=first) for name in
                ("trace", "lower", "compile", "first_call")}}
    if first is not None:
        parts["ramp"] = r["t0"] - first
        parts["waves running"] = r["setup_s"] - sum(parts.values())
    in_ramp = [] if first is None else [p for p in events if p["at"] >= first]
    H.log("setup_s from the inside: "
          + ", ".join(f"{k} {v:.2f}" for k, v in parts.items())
          + f" s of {r['setup_s']:.2f}; {len(events)} programs"
          + (f" ({len(in_ramp)} of them built inside the ramp)"
             if in_ramp else "") + ": "
          + ", ".join(f"{p['site'].split('#')[0]} "
                      f"({p['launch_rows']} rows, {p['slots_active']} slots)"
                      for p in events))
    return float(len(events))
