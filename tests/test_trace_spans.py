"""The program's own spans in the profiler's trace: every ``record()``
span is a ``jax.profiler.TraceAnnotation``, so a jax trace taken by
ANYONE (no ``profile()`` session armed) holds the serving cycle and the
train step on ``/host:CPU``, with their numbers; the launch counters
the engine notes into the cycle record; and the engine's own build and
its programs' builds (``startup/*``, ``program/*``)."""
import glob
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.models import GPTConfig, GPTForPretraining
from paddle_tpu.profiler import span as S
from paddle_tpu.serving import GenerationEngine

# the spans that carry a launch's number, in time order: its turn of the
# loop plans and dispatches it, the NEXT turn fetches and emits it
TURN = ["serving/sweep", "serving/admit", "serving/plan",
        "serving/decode_dispatch", "serving/record"]
LANDING = ["serving/host_fetch", "serving/emit"]
# an inactive span is one TraceMe that finds no trace running plus one
# bool check: 0.6-1 us here. The bound leaves room for a loaded test host
INACTIVE_SPAN_BOUND_US = 20.0


class _JaxTrace:
    """``with _JaxTrace(dir) as t:`` traces the block the way an outside
    caller does (``jax.profiler.start_trace``, no ``profile()``);
    ``t.host_events()`` then gives the ``/host:CPU`` events as
    ``(start_ns, end_ns, name, stats)``."""

    def __init__(self, trace_dir):
        self.dir = str(trace_dir)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the spans, not every call
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def host_events(self, prefix):
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        out = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        s = int(ev.start_ns)
                        out.append((s, s + int(ev.duration_ns), ev.name,
                                    dict(ev.stats)))
        return sorted(out, key=lambda e: (e[0], -e[1]))


@pytest.fixture(autouse=True)
def _no_session():
    S.reset()
    assert not S.is_active()
    yield
    assert not S.is_active()


def test_a_span_lands_in_a_jax_trace_with_no_session_armed(tmp_path):
    with _JaxTrace(tmp_path) as trace:
        with S.record("unit/outer", "user", args={"cycle": 7, "who": "me"}):
            with S.record("unit/inner", "user"):
                time.sleep(0.001)

        @S.record("unit/decorated", "user")
        def f():
            return 3

        assert f() == 3
    events = trace.host_events("unit/")
    assert [e[2] for e in events] == ["unit/outer", "unit/inner",
                                      "unit/decorated"]
    outer, inner, _ = events
    assert outer[3] == {"cycle": 7, "who": "me"}
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert inner[1] - inner[0] >= 1_000_000
    # the Python event buffer is the profile() session's alone
    assert S.events() == []


def test_with_no_trace_running_a_span_buffers_nothing_and_costs_little():
    n = 20_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            with S.record("unit/idle", "user", args={"cycle": i}):
                pass
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    assert S.events() == [] and S.dropped() == 0
    assert best < INACTIVE_SPAN_BOUND_US, f"{best:.2f} us per inactive span"


def test_a_session_still_buffers_the_same_spans(tmp_path):
    """Both at once: the armed buffer and the jax trace see one span."""
    with _JaxTrace(tmp_path) as trace:
        with S.profile():
            with S.record("unit/both", "user", args={"k": 1}):
                pass
    assert [e["name"] for e in S.events()] == ["unit/both"]
    assert [e[2:] for e in trace.host_events("unit/")] == \
        [("unit/both", {"k": 1})]


@pytest.fixture(scope="module")
def tiny_lm():
    paddle.seed(5)
    return GPTForPretraining(GPTConfig.tiny())


def _prompt(rng, n):
    return rng.randint(1, 50, size=n).astype(np.int32)


def test_serving_cycles_are_in_the_trace_with_their_children_in_order(
        tiny_lm, tmp_path):
    eng = GenerationEngine(tiny_lm, num_slots=2, max_len=32,
                           block_size=8,
                           prefill_budget=8)
    rng = np.random.RandomState(0)
    try:
        # warm: the traced cycles compile nothing
        for h in [eng.submit(_prompt(rng, n), max_new_tokens=3)
                  for n in (12, 3)]:
            h.result(timeout=300)
        before = {c["cycle"] for c in
                  eng.flight_recorder.snapshot()["cycles"]}
        with _JaxTrace(tmp_path) as trace:
            for h in [eng.submit(_prompt(rng, n), max_new_tokens=3)
                      for n in (12, 3)]:
                h.result(timeout=300)
        records = {c["cycle"]: c for c in
                   eng.flight_recorder.snapshot()["cycles"]
                   if c["cycle"] not in before}
    finally:
        eng.close()
    events = trace.host_events("serving/")
    cycles = [e for e in events if e[2] == "serving/cycle"]
    numbers = [e[3]["cycle"] for e in cycles]
    assert len(numbers) >= 3 and len(set(numbers)) == len(numbers)
    assert set(numbers) <= set(records)          # one span per cycle run
    turns = {e[3]["cycle"]: e for e in cycles}
    landed = 0
    for lo, hi, _, stats in cycles:
        n = stats["cycle"]
        mine = [e for e in events if e[3].get("cycle") == n
                and e[2] not in ("serving/cycle", "serving/wait")]
        names = [e[2] for e in mine]
        if names[:6] == TURN[:4] + LANDING:
            # the launch that opens a busy stretch lands in its own turn
            assert names[6:] == TURN[4:], (n, names)
            assert mine[-1][1] <= hi
        elif records[n]["decode_dispatch_ms"]:   # the turn launched
            assert names[:5] == TURN, (n, names)
            # ... and the launch lands a turn later, inside that turn,
            # after that turn's own dispatch if it has one
            # (the trace's end may cut the LAST traced cycle's landing
            # after its fetch: the result is handed over inside the emit)
            whole = (LANDING, LANDING[:1], []) if n == max(numbers) \
                else (LANDING, [])
            assert names[5:] in whole, (n, names)
            if names[5:] and n + 1 in turns:
                landed += 1
                assert turns[n + 1][0] <= mine[5][0] \
                    and mine[6][1] <= turns[n + 1][1]
                after = [e for e in events if e[3].get("cycle") == n + 1
                         and e[2] == "serving/decode_dispatch"]
                assert all(e[1] <= mine[5][0] for e in after)
        else:                  # nothing to launch: the turn only lands
            assert names in (
                ["serving/sweep", "serving/admit", "serving/plan",
                 "serving/record"],
                ["serving/sweep", "serving/admit", "serving/record"]), \
                (n, names)
        assert lo <= mine[0][0] and mine[:5][-1][1] <= hi
        for (_, end, _, _), (start, _, _, _) in zip(mine, mine[1:]):
            assert end <= start                  # in order, no overlap
    assert landed >= 2
    # the stretch between two cycles is a span too, before its cycle
    # (the first traced cycle's wait began before the trace did)
    waits = {e[3]["cycle"]: e for e in events if e[2] == "serving/wait"}
    for lo, _, _, stats in cycles[1:]:
        assert waits[stats["cycle"]][1] <= lo
    # the one child without a number is found by containment
    admits = [e for e in events if e[2] == "serving/admit"]
    prefills = [e for e in events if e[2] == "serving/prefill"]
    assert len(prefills) == 2
    for s, e, _, _ in prefills:
        assert any(lo <= s and e <= hi for lo, hi, _, _ in admits)
    assert S.events() == []                      # no session, no buffer


STARTUP = ["startup/engine_build", "startup/params", "startup/pallas_smoke",
           "startup/pool", "startup/plan_gate", "startup/scheduler"]
BUILD = ["program/trace", "program/lower", "program/compile",
         "program/first_call"]


@pytest.fixture(scope="module")
def a_traced_start(tiny_lm, tmp_path_factory):
    """A jax trace over an engine's construction and its first request:
    (the ``/host:CPU`` events by prefix, the engine's startup stats, its
    cycle records)."""
    with _JaxTrace(tmp_path_factory.mktemp("start")) as trace:
        eng = GenerationEngine(tiny_lm, num_slots=2, max_len=32,
                               block_size=8, prefill_budget=8,
                               hbm_budget_bytes=1 << 30)
        try:
            eng.submit(_prompt(np.random.RandomState(3), 5),
                       max_new_tokens=2).result(timeout=300)
        finally:
            eng.close()
    return (trace.host_events, eng.stats()["startup"],
            eng.flight_recorder.snapshot()["cycles"])


def test_the_engines_build_is_in_the_trace_part_by_part(a_traced_start):
    host_events, startup, _ = a_traced_start
    events = host_events("startup/")
    assert [e[2] for e in events] == STARTUP     # in time order, once each
    whole, parts = events[0], events[1:]
    for (_, end, _, _), (start, _, _, _) in zip(parts, parts[1:]):
        assert end <= start
    assert whole[0] <= parts[0][0] and parts[-1][1] <= whole[1]
    # the same clock stops as stats()["startup"], to a loaded host's hiccups
    assert (whole[1] - whole[0]) / 1e6 == pytest.approx(
        startup["build_ms"], abs=50.0)
    for (lo, hi, name, _) in parts:
        assert (hi - lo) / 1e6 == pytest.approx(
            startup["phases_ms"][name[len("startup/"):]], abs=50.0)


@pytest.mark.parametrize("part", BUILD)
def test_a_programs_build_lies_inside_the_dispatch_that_asked_for_it(
        a_traced_start, part):
    host_events, startup, cycles = a_traced_start
    # a span's ``site=`` writes the engine's ``#n`` as ``@n``: ``#`` ends
    # a TraceMe's arguments
    programs = {p["site"].replace("#", "@"): p
                for p in startup["programs"]}
    events = [e for e in host_events("program/") if e[2] == part]
    assert len(events) == len(programs) >= 1
    assert {e[3]["site"] for e in events} == set(programs)
    dispatches = {e[3]["cycle"]: e
                  for e in host_events("serving/decode_dispatch")}
    paid = {c["cycle"] for c in cycles if "built_ms" in c}
    key = {"program/first_call": "first_call_ms"}.get(
        part, part[len("program/"):] + "_ms")
    for lo, hi, _, stats in events:
        inside = [n for n, (s, e, _, _) in dispatches.items()
                  if s <= lo and hi <= e]
        assert len(inside) == 1 and inside[0] in paid
        assert (hi - lo) / 1e6 == pytest.approx(
            programs[stats["site"]][key], abs=50.0)
    # a launch that found its executable paid nothing and says nothing
    assert len(paid) == len(programs) < len(dispatches)


def test_the_cycle_record_carries_the_launch_as_it_was_built(tiny_lm):
    eng = GenerationEngine(tiny_lm, num_slots=2, max_len=32,
                           block_size=8,
                           prefill_budget=8)
    # (cycle, rows, sum of pos + rows, q blocks of 8 x KV blocks of 8,
    # q blocks of 8: at most 4 KV blocks here, one group of G = 16)
    planned = []
    build = eng._ragged_operands

    def spy(slot_requests, plan, spec=None, from_prev=()):
        live = {s: int(plan[s]) for s in slot_requests if plan.get(s, 0) > 0}
        ends = {s: eng._pool.slot_pos(s) + n for s, n in live.items()}
        planned.append((eng._sched._cycle, sum(live.values()),
                        sum(ends.values()),
                        sum(-(-n // 8) * -(-ends[s] // 8)
                            for s, n in live.items()),
                        sum(-(-n // 8) for n in live.values())))
        return build(slot_requests, plan, spec, from_prev)

    eng._ragged_operands = spy
    rng = np.random.RandomState(1)
    try:
        for h in [eng.submit(_prompt(rng, n), max_new_tokens=4)
                  for n in (13, 5)]:
            h.result(timeout=300)
    finally:
        eng.close()          # joins the scheduler: the last record is in
    records = {c["cycle"]: c for c in
               eng.flight_recorder.snapshot()["cycles"]}
    assert len(planned) >= 4
    for cycle, rows, kv, steps, fetches in planned:
        rec = records[cycle]
        for key in ("plan_ms", "emit_ms", "launch_rows", "launch_q",
                    "launch_t", "kv_tokens", "kv_steps", "kv_fetches",
                    "q_blocks", "q_blocks_wide", "kv_write_blocks",
                    "kv_walks", "kv_walks_handed"):
            assert key in rec, key
        # every walk of a launch but its first begins on a handed group
        # (these launches hold no pad block between real ones)
        assert rec["kv_walks_handed"] == rec["kv_walks"] - 1 >= 0
        assert rec["launch_rows"] == rows
        assert rec["kv_tokens"] == kv
        assert rec["kv_steps"] == steps
        assert rec["kv_fetches"] == fetches
        assert rec["launch_rows"] <= rec["launch_q"]
        # a slot's rows land in at least one block, at most one a row
        assert 1 <= rec["kv_write_blocks"] <= rec["launch_rows"]
        assert rec["launch_q"] % 8 == 0 and rec["launch_t"] >= 1
        assert rec["plan_ms"] > 0 and rec["emit_ms"] > 0
        # plan and launch are parts of the launch's own turn, whose
        # length the record keeps; its fetch and emit lie in the next
        assert rec["plan_ms"] + rec["decode_dispatch_ms"] <= rec["cycle_ms"]
    # chunk cycles and plain decode cycles both passed through
    assert any(records[c]["chunk_tokens"] for c, *_ in planned)
    assert any(not records[c]["chunk_tokens"] and r <= 2
               for c, r, *_ in planned)
    # a 13-token prompt in chunks of 8: the second chunk's one q block
    # walks both of the prompt's KV blocks
    assert any(steps > 1 and r <= 8 for _, r, _, steps, _ in planned)


def test_kv_fetches_counts_the_groups_a_launch_waits_for():
    """A hand-built launch where contexts pass one group: 150 prompt
    tokens at block 32 (G = 4 blocks a group) in chunks of 64 rows."""
    from paddle_tpu.ops.ragged_paged_attention import kv_group_blocks
    paddle.seed(5)
    cfg = GPTConfig.tiny()
    cfg.max_position_embeddings = 192
    eng = GenerationEngine(GPTForPretraining(cfg), num_slots=2, max_len=192,
                           block_size=32,
                           prefill_budget=64)
    heads = cfg.num_attention_heads
    assert kv_group_blocks(heads, 32, cfg.hidden_size // heads,
                           "float32") == 4
    try:
        eng.submit(_prompt(np.random.RandomState(2), 150),
                   max_new_tokens=3).result(timeout=300)
    finally:
        eng.close()
    walked = [(c["launch_rows"], c["kv_steps"], c["kv_fetches"],
               c["kv_write_blocks"], c["q_blocks_wide"], c["kv_walks"],
               c["kv_walks_handed"])
              for c in eng.flight_recorder.snapshot()["cycles"]
              if c.get("launch_rows")]
    # (rows, KV blocks the walks fetch, groups of 4 blocks they wait for,
    # blocks the rows land in, q blocks a wide step served, walks, walks
    # that began on a group the walk before them started): the chunks
    # end at 64, 128 and 150 tokens, then decode rows at 151, 152. A
    # 64-row chunk is two wide steps of 32 rows, each walking to its last
    # row's block (1 + 2, then 3 + 4 blocks, a group each); the 22-row
    # chunk's 3 q blocks share a step with a pad block and walk alone, 5
    # blocks = 2 groups each
    assert walked == [(64, 1 + 2, 2, 2, 8, 2, 1), (64, 3 + 4, 2, 2, 8, 2, 1),
                      (22, 3 * 5, 3 * 2, 1, 0, 3, 2), (1, 5, 2, 1, 0, 1, 0),
                      (1, 5, 2, 1, 0, 1, 0)]


def test_a_train_step_is_in_the_trace_with_its_number(tmp_path):
    rng = np.random.RandomState(0)
    net = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 4))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 4, (8, 1)).astype(np.int64)
    model.train_batch([x], [y])                  # step 1 builds and compiles
    with _JaxTrace(tmp_path) as trace:
        model.train_batch([x], [y])
        model.train_batch([x], [y])
    steps = [e for e in trace.host_events("hapi/")
             if e[2] == "hapi/train_batch"]
    assert [e[3] for e in steps] == [{"step": 2}, {"step": 3}]
    assert steps[0][1] <= steps[1][0]
    assert S.events() == []
