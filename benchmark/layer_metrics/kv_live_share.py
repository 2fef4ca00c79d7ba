"""KV pool: peak over the traced slice of blocks held by live page
tables (the flight recorder's ``blocks_in_use``: cached-but-released
blocks of the prefix trie do not count) over the pool's blocks, %."""


def read(r):
    used = [c["blocks_in_use"] for c in r.get("trace_cycles", [])
            if "blocks_in_use" in c]
    if not used:
        return None
    return 100.0 * max(used) / r["engine_stats"]["num_blocks"]
