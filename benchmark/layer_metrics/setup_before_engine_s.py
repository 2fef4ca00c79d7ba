"""Device: process start to the entry of the engine's constructor, s:
``engine_stats["startup"]["t_build"]`` (the program's stamp, on the clock
of ``t0``) less the process's start (``t0 - setup_s``). The benchmark's
own part of ``setup_s`` — imports, the TPU client, the weights made and
put on the device — and where a busy host shows first. Nothing where the
program stamps no ``t_build`` (every commit before PR 52)."""
from benchmark.layer_metrics import setup_programs_built as B


def read(r):
    st = B.startup(r)
    if st is None:
        return None
    return st["t_build"] - (r["t0"] - r["setup_s"])
