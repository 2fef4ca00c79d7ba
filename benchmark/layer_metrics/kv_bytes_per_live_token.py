"""KV pool: bytes of cache a live token costs — the blocks that live page
tables hold, every cache group (``kv_live_bytes`` of the cycle record),
over the tokens of the live contexts (``kv_live_tokens``), summed over
the slice's launches. A model of 2 global and 5 window layers at the
published widths pays ~7 KB when the window group frees what lies behind
the window (6,144 B of global rows as stored and a window's worth of
30,720 B spread over the context) and 36.9 KB held uniformly. A program
with one cache group does not stamp the counters."""


def read(r):
    cycles = [c for c in r.get("trace_cycles", [])
              if c.get("kv_live_tokens")]
    if not cycles:
        return None
    return sum(c["kv_live_bytes"] for c in cycles) \
        / sum(c["kv_live_tokens"] for c in cycles)
