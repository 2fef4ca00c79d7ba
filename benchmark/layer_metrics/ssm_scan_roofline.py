"""The recurrence of the state-space mixer: share of its roofline, %.

The least time of the slice's matched launches is the larger of two
(``lib/kernel_costs_falcon_h1.py``): the bytes the scan must move — every
advanced sequence's recurrent state read and written once a layer
(``state_slots`` of the launch's record) and the real rows' x, B, C, dt
and y (``ssm_rows``) — over the HBM bandwidth, and 5 FLOP a state element
a real row over the bf16 peak. Time is the device time UNDER SECTION
``ssm_scan`` of the same launches (``lib/launch_trace.py``: an op's
section is its ``tf_op`` scope path) — found by section, never by a
kernel's name, so it reads the same work whatever implements it, and
whatever the implementation does beyond the least (a second read of the
state, the padded rows, a chunk's quadratic form) lowers the share: it
cannot pass 100%. Should the scan become a Pallas kernel whose device
events keep no ``tf_op``, this reader must add that kernel's events to the
section's. None where the program stamps no ``state_slots`` or names no
such section (a program without the mixer)."""
from benchmark.lib import kernel_costs_falcon_h1 as KF
from benchmark.lib import launch_trace as LT
from benchmark.lib import peaks as P


def read(r):
    lt = LT.launch_trace(r)
    m = r.get("model", {})
    if lt is None or "sections" not in lt or "mamba_d_state" not in m:
        return None
    ns, by_bytes, by_flops = 0, 0.0, 0.0
    for n, rec in lt["records"].items():
        if "state_slots" not in rec:
            continue
        ns += lt["sections"][n].get("ssm_scan", 0)
        by_bytes += KF.scan_bytes(rec["state_slots"], rec["ssm_rows"], m)
        by_flops += KF.scan_flops(rec["ssm_rows"], m)
    if ns <= 0:
        return None
    peaks = P.peaks_for(r["device_kind"])
    least = max(by_bytes / peaks["hbm_bytes_per_s"],
                by_flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / (ns / 1e9)
