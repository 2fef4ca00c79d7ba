#!/usr/bin/env python3
"""Record ``benchmark/tests/data/launch-trace.xplane.pb`` and
``launch-trace-cycles.json``: a jax trace of about a dozen launches of a
small fused engine ON THE CHIP (two launches in flight; widths a TPU
compiles: 4 heads of 64), started while a request is already decoding —
so the slice's first launch was dispatched before the trace began — and
with a second request admitted inside it, so one launch carries a prompt
chunk. The trace is cut down (:func:`cut`) to what ``lib/launch_trace.py``
reads: the device plane's ops and module events with their metadata, the
host's ``serving/`` spans and the runtime's execute and enqueue events.
``python3 benchmark/tools/record_launch_trace.py <dir>``"""
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchmark.lib import system as SUT  # noqa: E402
from benchmark.lib import trace_reduce as TR  # noqa: E402
from benchmark.lib import traffic as T  # noqa: E402
from paddle_tpu.serving.scheduler import RequestCancelled  # noqa: E402
from record_host_spans import _fields, keep_events  # noqa: E402

SEED = 37
LAUNCHES = 12
MODEL = {"vocab_size": 512, "hidden_size": 256, "num_hidden_layers": 2,
         "num_attention_heads": 4, "intermediate_size": 1024,
         "max_position_embeddings": 2048, "hidden_dropout_prob": 0.0,
         "attention_dropout_prob": 0.0, "initializer_range": 0.02,
         "weight_std": 0.25}
SERVING = {"dtype": "bfloat16", "block_size": 16, "max_len": 2048,
           "prefill_budget": 64, "max_queue": 16, "pool_blocks": 512}
KEEP = ("%", "jit_", "serving/", "DoEnqueueProgram", "PjitFunction(",
        "PJRT_LoadedExecutable_Execute")
# the plane that holds each program's whole HLO proto (named ``jit_…`` too)
DROP_PLANE = b"/host:metadata"
RECORD_KEYS = ("cycle", "t", "chunk_tokens", "emitted", "launch_rows",
               "launch_q", "launch_t", "launch_program", "overlapped")


def cut(xspace: bytes) -> bytes:
    """The trace with only the events ``KEEP`` names, less ``DROP_PLANE``
    (XSpace.planes = 1, XPlane.name = 2)."""
    kept = keep_events(xspace, lambda name: name.startswith(KEEP))
    return b"".join(
        raw for number, value, raw in _fields(kept)
        if number != 1 or dict((n, v) for n, v, _ in _fields(value)).get(2)
        != DROP_PLANE)


def main(out_dir: str) -> int:
    import jax
    served = SUT.Served(SUT.build_lm(MODEL, SEED, SERVING["dtype"]), MODEL,
                        SERVING, slots=4)
    vocab = int(MODEL["vocab_size"])
    prompt = lambda i, n: T.prompt_tokens(SEED, i, n, vocab, stream=7)
    last = lambda: served.engine.flight_recorder.snapshot()["cycles"][-1][
        "cycle"]

    # every program the slice uses: a long decode beside a 40-token
    # prompt. A context of 300-500 tokens keeps the page table in ONE
    # bucket (t32), so the slice runs three programs (one decode row, the
    # chunk, two decode rows) and the data file stays small
    long_one = served.engine.submit(prompt(0, 300), 150)
    stream = long_one.stream()
    for _ in range(8):
        next(stream)
    served.generate([prompt(1, 40)], [8])
    for _ in stream:
        pass

    served.start_cycle_poll(every_s=0.02)
    long_one = served.engine.submit(prompt(2, 300), 1600)
    stream = long_one.stream()

    def drain():
        try:
            for _ in stream:
                pass
        except RequestCancelled:     # the tool's own cancel, below
            pass

    drain = threading.Thread(target=drain)
    for _ in range(8):
        next(stream)
    drain.start()
    jax.profiler.start_trace(out_dir)
    first = last()
    served.engine.submit(prompt(3, 40), 8)
    while last() < first + LAUNCHES:
        time.sleep(0.001)
    jax.profiler.stop_trace()
    long_one.cancel()
    drain.join(timeout=60)
    cycles = served.stop_cycle_poll()
    served.close()

    path = TR.latest_xplane(out_dir)
    with open(path, "rb") as f:
        whole = f.read()
    small = os.path.join(out_dir, "launch-trace.xplane.pb")
    with open(small, "wb") as f:
        f.write(cut(whole))
    # stopping the trace takes a second of launches nobody traced
    kept = [{k: c[k] for k in RECORD_KEYS if k in c} for c in cycles
            if first - 4 <= c["cycle"] <= first + LAUNCHES + 4]
    with open(os.path.join(out_dir, "launch-trace-cycles.json"), "w") as f:
        json.dump(kept, f, indent=1)
    print(f"traced from cycle {first}; wrote {small} "
          f"({os.path.getsize(small):,} of {len(whole):,} bytes) and {len(kept)} records "
          f"(launch-trace-cycles.json)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
