"""Kernel ``ragged_paged_attention``: share of the slice's real q blocks
that a WIDE grid step served, %: sum of ``q_blocks_wide`` over sum of
``q_blocks`` of the slice's cycle records (the engine's counters, taken
where the launch is built, from the launch's ``blk_seq`` by the function
the kernel itself decides by). A grid step covers M q blocks; where all M
are rows of one sequence — the inside of a prompt chunk — it walks the
page table once for all of them, and elsewhere (decode rows, a chunk's
first and last q blocks, pad blocks) once a q block. It says how often
the mechanism engages: ~half of the q blocks of a launch that carries a
1,024-row chunk beside ~120 decode rows, none of a launch of decode rows.
Nothing where no record has ``q_blocks`` (a program from before wide
steps, or a kernel that has none)."""
from benchmark.lib import host_spans as HS


def read(r):
    counted = [c for c in HS.slice_records(r) if c.get("q_blocks")]
    if not counted:
        return None
    return 100.0 * sum(c.get("q_blocks_wide", 0) for c in counted) \
        / sum(c["q_blocks"] for c in counted)
