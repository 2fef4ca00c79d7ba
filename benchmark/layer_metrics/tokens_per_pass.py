"""Scheduler, block generation: tokens emitted over the slot-passes that
made them, across the slice's launches: sum of ``emitted`` over sum of
``denoise_slots + commit_slots`` of the cycle records (the scheduler's
counters, taken where a launch lands). A block of 4 takes 4 denoising
passes and one commit: 0.8 by arithmetic while commits ride alone, a
little over it since a request's last block takes no commit, 1.0 if a
commit rode with the next block's first pass. A program that yields one
token a step counts no passes: nothing to read."""


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "denoise_slots" in c]
    passes = sum(c["denoise_slots"] + c["commit_slots"] for c in cycles)
    if not passes:
        return None
    return sum(c["emitted"] for c in cycles) / passes
