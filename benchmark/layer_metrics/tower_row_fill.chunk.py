"""Engine and steps: ``tower_row_fill`` of the slice's cycles that carried
a prompt chunk beside the decode rows: real rows over the rows the
program's tower runs on (the decode rows and a chunk budget, rounded up to
whole MXU passes), %. Nothing if the slice holds no such cycle, or where
the program keeps no ``launch_tower_rows`` (every commit before PR 41)."""
from benchmark.lib import host_spans as HS


def read(r):
    counted = [c for c in HS.slice_records(r) if c.get("launch_tower_rows")
               and c.get("chunk_tokens", 0) > 0]
    if not counted:
        return None
    return 100.0 * sum(c["launch_rows"] for c in counted) \
        / sum(c["launch_tower_rows"] for c in counted)
