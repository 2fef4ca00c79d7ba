"""Scheduler, block generation: share of the slice's block commits that
rode with the next block's first denoising pass, %: sum of ``ride_slots``
over sum of ``ride_slots + commit_slots`` of the cycle records (the
scheduler's counters, taken where a launch lands; ``commit_slots`` counts
the commits that rode alone, a launch of their slot that yields no
token). 100 when every finished block but a request's last is committed
by the launch that opens the next one. A program whose records have no
``ride_slots`` (one token a step, or a commit before rides existed) has
nothing to read."""


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "ride_slots" in c]
    commits = sum(c["ride_slots"] + c["commit_slots"] for c in cycles)
    if not commits:
        return None
    return 100.0 * sum(c["ride_slots"] for c in cycles) / commits
