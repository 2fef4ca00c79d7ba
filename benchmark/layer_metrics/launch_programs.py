"""Engine and steps: how many different step programs the window's
launches ran: distinct ``(launch_q, launch_t)`` pairs over the cycle
records of the measured window (the engine's counters: the row and
page-table buckets of the program each launch was built for; a
record's ``t`` is on the clock of the window's ``t0`` and ``t1``).
Each is a program the warm-up has to name and retrieve or compile
before the window opens, about 2 s of ``setup_s`` warm, and one the
window launches without its being warmed is a compile inside it."""


def read(r):
    lo, hi = r.get("t0", float("-inf")), r.get("t1", float("inf"))
    programs = {(c["launch_q"], c["launch_t"]) for c in r.get("cycles", [])
                if c.get("launch_q") and lo <= c["t"] < hi}
    return float(len(programs)) if programs else None
