"""Engine and steps: real query rows over the rows the launched program's
TOWER runs on, %, on plain decode cycles: sum of ``launch_rows`` over sum
of ``launch_tower_rows`` of the slice's cycle records that carried no
prompt chunk (the engine's counters, taken where the launch is built).
Everything of a step but its attention kernel — norms, projections, the
cache write, the mixer, the FFN, the head's gather — runs on the tower's
rows, which hold the slots' real rows back to back, rounded up to whole
MXU passes; ``q_row_fill`` stays the fill of the KERNEL's rows, each
slot's padded to its q blocks. Nothing where the program keeps no such
counter (a tower on the kernel's rows: every commit before PR 41).
``tower_row_fill.chunk`` reads the cycles with a chunk."""
from benchmark.lib import host_spans as HS


def read(r):
    counted = [c for c in HS.slice_records(r) if c.get("launch_tower_rows")
               and not c.get("chunk_tokens", 0) > 0]
    if not counted:
        return None
    return 100.0 * sum(c["launch_rows"] for c in counted) \
        / sum(c["launch_tower_rows"] for c in counted)
