"""Scheduler: the first client request to the window's start, s: ``t0``
less ``door_stats["first_request_t"]`` (the front door's stamp of the
first completion request; the warm-up waves go through ``engine.submit``
and do not pass it). The load generator's ramp, ``lead_s`` included: the
part of ``setup_s`` that reads 7.6 s or 45.8 s by the host's load alone.
Nothing where the door stamps no request (every commit before PR 52), or
saw none."""


def read(r):
    first = r.get("door_stats", {}).get("first_request_t")
    if first is None:
        return None
    return r["t0"] - first
