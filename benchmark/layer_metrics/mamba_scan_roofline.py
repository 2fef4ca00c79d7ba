"""The recurrence of a Mamba-2 mixer that is a block's whole content
(Nemotron-H's ``M`` blocks): share of its roofline, %.

The least time of the slice's matched launches is the larger of two
(``lib/kernel_costs_nemotron_h.py``): the bytes the scan must move — every
advanced sequence's recurrent state read and written once (``state_slots``
of the launch's record) and the real rows' x, B, C, dt and y
(``ssm_rows``), a block with state, and those blocks are the record's
``state_layers`` (5 of this stage's 11) — over the HBM bandwidth, and 5
FLOP a state element a real row over the bf16 peak. Time is the device
time UNDER SECTION ``ssm_scan`` of the same launches
(``lib/launch_trace.py``), found by section and never by a kernel's name,
as ``ssm_scan_roofline`` finds it; whatever the implementation does beyond
the least lowers the share. (That reader takes Falcon-H1's key names and
multiplies by ``num_hidden_layers``: 2.2x over here, so a cell lists one
of the two.) None where the program stamps no ``state_layers`` (every
layer has a state, or the parent) or names no such section."""
from benchmark.lib import kernel_costs_nemotron_h as KN
from benchmark.lib import launch_trace as LT
from benchmark.lib import peaks as P


def read(r):
    lt = LT.launch_trace(r)
    m = r.get("model", {})
    if lt is None or "sections" not in lt or "ssm_state_size" not in m:
        return None
    ns, by_bytes, by_flops = 0, 0.0, 0.0
    for n, rec in lt["records"].items():
        if "state_slots" not in rec or "state_layers" not in rec:
            continue
        ns += lt["sections"][n].get("ssm_scan", 0)
        by_bytes += KN.scan_bytes(rec["state_slots"], rec["ssm_rows"],
                                  rec["state_layers"], m)
        by_flops += KN.scan_flops(rec["ssm_rows"], rec["state_layers"], m)
    if ns <= 0:
        return None
    peaks = P.peaks_for(r["device_kind"])
    least = max(by_bytes / peaks["hbm_bytes_per_s"],
                by_flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / (ns / 1e9)
