"""Engine and steps: device-busy ms that a prompt chunk adds to a
launch, per 1,000 kernel steps (so us a step): over the slice's cycles whose record
has ``chunk_tokens > 0``, what their launches took above
``decode_step_ms`` (the plain decode launch of the same slice), over the
steps they walked above it (the record's ``kv_steps``, the engine's
counter of (q block, KV block) pairs, times heads and layers). By the
step, because a chunk's cost goes with the square of its size, so the
whole launch's time and the time a chunk token both go with the chunks
that happen to fall in an 11-cycle slice; less the plain launch, so that
a faster decode row moves ``decode_step_ms`` and not this. The line
before it gives the plain launch's own time a step. Nothing if the
slice holds no such cycle, or no plain one."""
from benchmark.lib import harness as H
from benchmark.lib import host_spans as HS


def read(r):
    if "model" not in r:
        return None
    m = r["model"]
    us = HS.chunk_step_us(r, int(m["num_attention_heads"])
                          * int(m["num_hidden_layers"]))
    if us is None:
        return None
    H.log(f"device us a kernel step: plain decode launch "
          f"{us['plain']:.4f}, a chunk's steps above it {us['chunk']:.4f}")
    return us["chunk"]
