"""KV pool: bytes a live token costs where a sequence holds a recurrent
state beside its cache — the state rows of the live slots
(``state_live_bytes`` of the cycle record) plus the blocks that live page
tables hold (``kv_live_bytes``), over the tokens of the live contexts
(``kv_live_tokens``), summed over the slice's launches. A state's bytes do
not grow with the context, so the figure falls as contexts grow: at the
published widths 25.5 MB of state a slot beside 12,288 B of K and V a
token is ~35 KB a token at 1.1 k live tokens a slot. None where the
program stamps no ``state_live_bytes`` (a model without state)."""


def read(r):
    cycles = [c for c in r.get("trace_cycles", [])
              if c.get("kv_live_tokens") and "state_live_bytes" in c]
    if not cycles:
        return None
    return sum(c["state_live_bytes"] + c["kv_live_bytes"] for c in cycles) \
        / sum(c["kv_live_tokens"] for c in cycles)
