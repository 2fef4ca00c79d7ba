"""Engine and steps: median device-busy ms of one launch that carries
decode rows only: over the slice's cycles whose record has
``chunk_tokens == 0``, the device-busy time between that cycle's
``serving/decode_dispatch`` start and ``serving/host_fetch`` end (device
trace, joined to the flight recorder's record by cycle number). The
line before it says whether the spans and the device ops share a
clock."""
from benchmark.lib import harness as H
from benchmark.lib import host_spans as HS


def read(r):
    check = HS.clock_check(r)
    if check is not None:
        H.log(f"clock check, host spans against device ops: {check}")
    return HS.decode_step_ms(r)
