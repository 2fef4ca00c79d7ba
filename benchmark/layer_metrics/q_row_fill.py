"""Engine and steps: real query rows over the rows the launched program
computes, %, on plain decode cycles: sum of ``launch_rows`` over sum of
``launch_q`` of the slice's cycle records that carried no prompt chunk
(the engine's counters, taken where the launch is built). Every slot's
rows are padded to the kernel's row block and the total to a power of
two. ``q_row_fill.chunk`` reads the cycles with a chunk."""
from benchmark.lib import host_spans as HS


def read(r):
    return HS.row_fill(r, chunk=False)
