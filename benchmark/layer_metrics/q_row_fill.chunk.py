"""Engine and steps: ``q_row_fill`` of the slice's cycles that carried a
prompt chunk beside the decode rows: the chunk's rows are real, so these
launches are fuller, by how much goes with the chunks' sizes. Nothing if
the slice holds no such cycle."""
from benchmark.lib import host_spans as HS


def read(r):
    return HS.row_fill(r, chunk=True)
