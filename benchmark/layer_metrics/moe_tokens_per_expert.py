"""Expert layer: tokens a held expert gets in one launch, averaged over
the slice's launches: ``moe_pairs`` / (experts held x expert layers x
launches). 128 decode rows choosing 8 of 192 give 5.3; a launch that also
carries a 1,024-row chunk gives nine times that."""
from benchmark.lib import kernel_costs_axk1 as KA


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "moe_pairs" in c]
    if not cycles:
        return None
    held, layers = KA.held_expert_layers(r["model"])
    return sum(c["moe_pairs"] for c in cycles) / (held * layers * len(cycles))
