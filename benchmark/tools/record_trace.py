#!/usr/bin/env python3
"""Record the small trace ``benchmark/tests/data/small.xplane.pb`` was
taken from: three named matmul steps with a sleep between them, on
whatever device jax has. ``python3 benchmark/tools/record_trace.py <dir>``"""
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import trace_reduce as TR  # noqa: E402


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def small_step(x):
        with jax.named_scope("small_step_body"):
            return jnp.tanh(x @ x) * 0.5

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_step(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    t0 = time.monotonic()
    for _ in range(3):
        x = small_step(x)
        x.block_until_ready()
        time.sleep(0.01)
    window = time.monotonic() - t0
    jax.profiler.stop_trace()
    path = TR.latest_xplane(out_dir)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    print(f"window_s {window:.6f}; wrote {out_dir}/small.xplane.pb "
          f"({os.path.getsize(path):,} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
