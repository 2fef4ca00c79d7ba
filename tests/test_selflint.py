"""Repo self-lint (paddle_tpu/analysis/selflint.py) runs green as a
tier-1 gate, and each AST rule provably catches its seeded violation —
a lint that cannot fail is not a lint."""
from paddle_tpu.analysis.selflint import lint_repo, lint_source


def test_repo_is_lint_clean():
    findings = lint_repo()
    assert not findings, "\n".join(str(f) for f in findings)


def test_device_get_rule():
    src = "import jax\ndef f(x):\n    return jax.device_get(x)\n"
    hot = lint_source("t.py", src, "framework/dispatch.py")
    assert [f.rule for f in hot] == ["device-get-hot-path"]
    assert hot[0].line == 3
    # the same call OUTSIDE a hot-path module is a legitimate sync point
    assert lint_source("t.py", src, "distributed/spmd.py") == []
    # suppression comment with an adjacent justification is honored
    sup = src.replace("jax.device_get(x)", "jax.device_get(x)  # lint: ok")
    assert lint_source("t.py", sup, "framework/dispatch.py") == []


def test_monitor_lock_rules():
    out = lint_source(
        "t.py", "from paddle_tpu.framework.monitor import _lock\n",
        "hapi/model.py")
    assert [f.rule for f in out] == ["monitor-lock-contract"]
    # inside monitor.py: stat_add must stay lock-free
    src = ("def stat_add(name, value=1):\n"
           "    with _lock:\n        pass\n")
    out = lint_source("t.py", src, "framework/monitor.py")
    assert [f.rule for f in out] == ["monitor-lock-contract"]
    # ...but other functions there may lock (readers do, by contract)
    src_ok = ("def stat_get(name):\n"
              "    with _lock:\n        return 0\n")
    assert lint_source("t.py", src_ok, "framework/monitor.py") == []


def test_serving_host_sync_rule():
    src = ("import jax\n"
           "def loop(x):\n"
           "    a = jax.device_get(x)\n"          # flagged
           "    b = x.numpy()\n"                  # flagged
           "    c = x.block_until_ready()\n"      # flagged
           "    return a, b, c\n")
    out = lint_source("t.py", src, "serving/scheduler.py")
    assert [f.rule for f in out] == ["serving-host-sync"] * 3
    assert [f.line for f in out] == [3, 4, 5]
    # the rule covers the PAGED memory manager too (serving/paging.py is
    # scheduler-thread host bookkeeping — a sync there stalls every
    # decode cycle exactly like one in the loop), and the module form
    # jax.block_until_ready(x) is flagged like the method form
    paged_src = ("import jax\n"
                 "def ensure_writable(x):\n"
                 "    return jax.block_until_ready(x)\n")
    out = lint_source("t.py", paged_src, "serving/paging.py")
    assert [f.rule for f in out] == ["serving-host-sync"]
    assert "jax.block_until_ready" in out[0].message
    # ...and the ISSUE-6 tracing/flight-recorder modules by
    # construction: host-time stamping lives in serving/, so a stray
    # sync slipped into the trace path is flagged like one in the loop
    trace_src = ("import jax\n"
                 "def stamp(x):\n"
                 "    return x.numpy()\n")
    out = lint_source("t.py", trace_src, "serving/tracing.py")
    assert [f.rule for f in out] == ["serving-host-sync"]
    out = lint_source("t.py", trace_src, "serving/flight_recorder.py")
    assert [f.rule for f in out] == ["serving-host-sync"]
    # the same calls OUTSIDE the serving package are unflagged (the
    # gather-and-run batcher in inference/serving.py blocks by design)
    assert lint_source("t.py", src, "inference/serving.py") == []
    # the windowed-fetch exception is suppressible
    sup = src.replace("jax.device_get(x)", "jax.device_get(x)  # lint: ok")
    out = lint_source("t.py", sup, "serving/engine.py")
    assert [f.line for f in out] == [4, 5]


def test_ops_handler_sync_rule():
    # the scrape-only ops surface: ANY jax/jnp call and the scheduler-
    # blocking reads are banned in serving/opsserver.py + serving/slo.py
    src = ("import jax\n"
           "import jax.numpy as jnp\n"
           "def handler(h, x):\n"
           "    a = jnp.asarray(x)\n"              # flagged: jnp call
           "    b = h.result()\n"                  # flagged: blocks sched
           "    return a, b\n")
    out = lint_source("t.py", src, "serving/opsserver.py")
    assert [f.rule for f in out] == ["ops-handler-sync"] * 2
    assert [f.line for f in out] == [4, 5]
    out = lint_source("t.py", src, "serving/slo.py")
    assert [f.rule for f in out] == ["ops-handler-sync"] * 2
    # a device fetch in these files trips BOTH walks: the package-wide
    # serving-host-sync rule and this one (the contracts compose)
    fetch = "import jax\ndef h(x):\n    return jax.device_get(x)\n"
    rules = sorted(f.rule for f in
                   lint_source("t.py", fetch, "serving/opsserver.py"))
    assert rules == ["ops-handler-sync", "serving-host-sync"]
    # elsewhere in serving/ the result() read is the legitimate caller
    # surface (engine.submit().result()) and stays unflagged
    ok = "def wait(h):\n    return h.result()\n"
    assert lint_source("t.py", ok, "serving/engine.py") == []
    # suppression honored like every other rule
    sup = src.replace("h.result()", "h.result()  # lint: ok")
    out = lint_source("t.py", sup, "serving/opsserver.py")
    assert [f.line for f in out] == [4]


def test_memory_stats_hot_path_rule():
    # polling device memory stats inside the serving package is a PjRt
    # query on the scheduler hot path — both the method and bare-name
    # call forms are flagged
    src = ("from paddle_tpu import device\n"
           "def cycle(d):\n"
           "    a = device.memory_stats()\n"        # flagged
           "    b = memory_stats()\n"               # flagged
           "    return a, b\n")
    out = lint_source("t.py", src, "serving/scheduler.py")
    assert [f.rule for f in out] == ["memory-stats-hot-path"] * 2
    assert [f.line for f in out] == [3, 4]
    # host-only watermarks (profiler.memory.mark) are the sanctioned
    # path and are not flagged
    ok = ("from paddle_tpu.profiler import memory as _memory\n"
          "def cycle(n):\n"
          "    _memory.mark('serving/cycle', cycle=n)\n")
    assert lint_source("t.py", ok, "serving/scheduler.py") == []
    # the same poll OUTSIDE serving/ (the sampler thread's home, fit's
    # windowed flush) is legitimate
    assert lint_source("t.py", src, "profiler/memory.py") == []
    # suppression with an argued justification is honored
    sup = src.replace("device.memory_stats()",
                      "device.memory_stats()  # lint: ok")
    out = lint_source("t.py", sup, "serving/engine.py")
    assert [f.line for f in out] == [4]


def test_numerics_host_sync_rule():
    # the numerics audit module must never sync: its whole point is
    # replacing the reference's per-op host sweep with audits fetched
    # only at fit's flush windows — device_get/.item()/.numpy()/
    # .block_until_ready anywhere in profiler/numerics.py is the bug
    # class the rule exists to catch
    src = ("import jax\n"
           "def flush(x):\n"
           "    a = jax.device_get(x)\n"          # flagged
           "    b = x.item()\n"                   # flagged
           "    c = x.numpy()\n"                  # flagged
           "    d = jax.block_until_ready(x)\n"   # flagged
           "    return a, b, c, d\n")
    out = lint_source("t.py", src, "profiler/numerics.py")
    assert [f.rule for f in out] == ["numerics-host-sync"] * 4
    assert [f.line for f in out] == [3, 4, 5, 6]
    # the fetch site itself (hapi/model.py np.asarray at the flush) and
    # the rest of the profiler package are out of the rule's scope
    assert lint_source("t.py", src, "profiler/span.py") == []
    assert lint_source("t.py", src, "profiler/memory.py") == []
    # an argued suppression is honored, like every other rule
    sup = src.replace("x.item()", "x.item()  # lint: ok")
    out = lint_source("t.py", sup, "profiler/numerics.py")
    assert [f.line for f in out] == [3, 5, 6]


def test_pallas_block_tiling_rule():
    """The Mosaic block-tiling bug class as a standing static check: a literal
    BlockSpec dim that violates the Mosaic (8, 128) rule is flagged in
    ops/; legal shapes, SMEM specs, shapeless specs, dynamic dims and
    argued suppressions are not."""
    # the exact r02 crash: (1, 128) block over a [BH, S] array — the
    # second-to-last literal 1 is neither 8-divisible nor the array dim
    src = ("import jax.experimental.pallas as pl\n"
           "spec = pl.BlockSpec((1, 128), lambda i: (i, 0))\n")
    out = lint_source("t.py", src, "ops/pallas_kernels.py")
    assert [f.rule for f in out] == ["pallas-block-tiling"]
    assert out[0].line == 2
    # a misaligned literal LAST dim is the other half of the rule
    out = lint_source(
        "t.py",
        "import jax.experimental.pallas as pl\n"
        "spec = pl.BlockSpec((8, 64), lambda i: (i, 0))\n",
        "ops/pallas_kernels.py")
    assert [f.rule for f in out] == ["pallas-block-tiling"]
    # both legal jax spellings are covered: the bare-name import form
    # and the block_shape= keyword form
    out = lint_source(
        "t.py",
        "from jax.experimental.pallas import BlockSpec\n"
        "a = BlockSpec((1, 128), lambda i: (i, 0))\n"
        "b = BlockSpec(block_shape=(1, 128), index_map=lambda i: (i, 0))\n",
        "ops/pallas_kernels.py")
    assert [f.rule for f in out] == ["pallas-block-tiling"] * 2
    assert [f.line for f in out] == [2, 3]
    # legal literals (8-divisible sublane, 128-aligned lane) pass, as
    # do leading dims of >2D blocks (only the last two are tiled)
    ok = ("import jax.experimental.pallas as pl\n"
          "a = pl.BlockSpec((8, 128), lambda i: (i, 0))\n"
          "b = pl.BlockSpec((1, 128, 256), lambda i: (i, 0, 0))\n")
    assert lint_source("t.py", ok, "ops/pallas_kernels.py") == []
    # dynamic dims are trusted (derived from array shapes at runtime),
    # SMEM specs and shapeless whole-array specs are out of scope
    ok2 = ("import jax.experimental.pallas as pl\n"
           "from jax.experimental.pallas import tpu as pltpu\n"
           "a = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))\n"
           "b = pl.BlockSpec((1, 1), memory_space=pltpu.SMEM)\n"
           "c = pl.BlockSpec(memory_space=pltpu.ANY)\n")
    assert lint_source("t.py", ok2, "ops/pallas_kernels.py") == []
    # outside ops/ the rule does not apply...
    assert lint_source("t.py", src, "serving/engine.py") == []
    # ...and a block-equals-array-dim case is suppressible with an
    # argued '# lint: ok' (the fused-LN [1, D] param specs)
    sup = src.replace("lambda i: (i, 0))",
                      "lambda i: (i, 0))  # lint: ok")
    assert lint_source("t.py", sup, "ops/pallas_kernels.py") == []


def test_asarray_rule():
    src = (
        "import numpy as np\n"
        "from .registry import register_op\n"
        "@register_op('foo')\n"
        "def _foo(x):\n"
        "    return np.asarray(x) + 1\n"          # flagged: jit op
        "@register_op('bar', jit=False)\n"
        "def _bar(x):\n"
        "    return np.asarray(x) + 1\n"          # ok: host-side op
        "@register_op('baz')\n"
        "def _baz(x):\n"
        "    def cb(x):\n"
        "        return np.asarray(x)\n"          # ok: shadowed (callback)
        "    return cb\n")
    out = lint_source("t.py", src, "ops/foo_ops.py")
    assert [(f.rule, f.line) for f in out] == [("asarray-on-traced", 5)]


def test_metric_naming_rule():
    """ISSUE-13: literal metric names at monitor/registry write sites
    are snake_case paths with units in the suffix — each violation
    class fires, each idiom in use stays green."""
    # seeded violations
    for src in (
        'stat_observe("serving/TTFT-Time", 1.0)\n',      # case + dash
        'stat_add("cache size", 3)\n',                   # space
        'stat_observe("op_decode_time", 3)\n',           # unitless time
        'stat_observe("hapi/step_latency", 3)\n',
        'stat_add("pool_gb", 3)\n',                      # scaled size
        'metrics.inc("servingRequests")\n',              # camelCase
        '_metrics.set_gauge("Queue_Depth", 1)\n',
    ):
        out = lint_source("t.py", src, "serving/engine.py")
        assert [f.rule for f in out] == ["metric-naming"], (src, out)
    # the repo's live idioms stay green
    for src in (
        'stat_observe("serving/ttft_ms", 1.0)\n',
        'stat_observe(f"op_time_ms/{name}", t)\n',       # literal head
        'stat_add(f"collective_bytes/{kind}", n)\n',
        'stat_add("serving/tokens_per_sec", 3)\n',       # a rate, not secs
        'stat_observe("memory/bytes_in_use", 3)\n',
        'x.observe("Whatever Name", 1)\n',   # not a metrics alias
        'stat_observe(name, t)\n',           # fully dynamic: out of scope
    ):
        out = [f for f in lint_source("t.py", src, "serving/engine.py")
               if f.rule == "metric-naming"]
        assert out == [], (src, out)
    # suppression honored
    sup = 'stat_observe("op_decode_time", 3)  # lint: ok\n'
    assert lint_source("t.py", sup, "serving/engine.py") == []


def test_analysis_no_device_rule():
    """ISSUE 18: paddle_tpu/analysis must stay a pure TRACE-level
    layer — the fit-before-compile planner's zero-compile guarantee
    rests on no device/compile API ever creeping into it."""
    src = ("import jax\n"
           "def plan(fn, x):\n"
           "    jitted = jax.jit(fn)\n"
           "    exe = jitted.lower(x).compile()\n"
           "    y = jax.device_put(x)\n"
           "    return y.block_until_ready()\n")
    out = lint_source("t.py", src, "analysis/liveness.py")
    assert [f.rule for f in out] == ["analysis-no-device"] * 4
    assert [f.line for f in out] == [3, 4, 5, 6]
    # the same calls OUTSIDE analysis/ are someone else's business
    # (other rules may flag them for their own reasons, this one not)
    other = lint_source("t.py", src, "framework/program_registry.py")
    assert not [f for f in other if f.rule == "analysis-no-device"]
    # re.compile is text processing, not XLA
    ok = "import re\nPAT = re.compile(r'x+')\n"
    assert lint_source("t.py", ok, "analysis/core.py") == []
    # suppression with justification is honored, line by line
    sup = src.replace("jax.device_put(x)",
                      "jax.device_put(x)  # lint: ok")
    out = lint_source("t.py", sup, "analysis/liveness.py")
    assert 5 not in [f.line for f in out]
    assert [f.line for f in out] == [3, 4, 6]


def test_host_tier_promoter_covered_by_construction():
    """PR 20 seeded check: the host tier lives in serving/, so a stray
    blocking fetch in the PROMOTER body (the H2D path that must stay
    async) is caught by serving-host-sync by construction — and the one
    sanctioned copy, the spiller's batched demotion fetch, is exactly
    the suppressed form host_tier.py ships."""
    src = ("import jax\n"
           "import numpy as np\n"
           "def _promote_loop(self, tk, entries):\n"
           "    staged = jax.device_put(np.stack(entries, axis=2))\n"
           "    return jax.device_get(staged)\n")      # flagged: sync H2D
    out = lint_source("t.py", src, "serving/host_tier.py")
    assert [f.rule for f in out] == ["serving-host-sync"]
    assert out[0].line == 5
    # the sanctioned spiller copy is the suppressed form
    ok = ("import jax\n"
          "import numpy as np\n"
          "def _fetch(self, dev):\n"
          "    return np.asarray(jax.device_get(dev))  # lint: ok\n")
    assert lint_source("t.py", ok, "serving/host_tier.py") == []
