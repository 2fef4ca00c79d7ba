"""The warm-up waves of the benchmark's serving traffic files reach every
``(Q, T)`` program their windows launch — rehearsed without a model over
the REAL scheduler, pool and ``engine._ragged_operands``
(``tests/_mock_serving.py:RaggedMockDevice``).

Since PR 41 a launch takes the smallest ``Q`` bucket whose kernel rows
hold its padded rows AND whose tower rows hold its real ones
(``engine._launch_bucket``), so a chunk with few decode rows beside it
runs one bucket up from where it ran before. Warm-up names programs by
sending requests that make them (``benchmark/lib/serve.py:_warm``), and a
program the window meets first is a compile inside the window
(``compiles.serve``): the rule has to fit the files as they are.
"""
import json
import os
import time

import pytest

from _mock_serving import RaggedMockDevice, mock_pool
from paddle_tpu.serving.scheduler import GenerationRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (traffic file, its configuration): the cells of BENCHMARK.json that
# generate a token a row. `blocks-backlog-s128` (sdar: blocks of 8 rows a
# slot) launches the programs it launched — R(Q) == Q in each of its
# buckets, tests/test_compact_tower.py
CELLS = [("decode-backlog", "gpt2-large"),
         ("decode-backlog-s128", "axk1-ep16"),
         ("long-backlog-s128", "mimo-v2-flash-ep16"),
         ("long-backlog-s128", "lfm2-24b-a2b-pp4"),
         ("decode-heavy-backlog-s64", "falcon-h1-34b-pp12"),
         ("decode-heavy-backlog-s128", "longcat-flash-ep32")]


def _load(traffic, config):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           f"{traffic}.json")) as f:
        t = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{config}.json")) as f:
        serving = json.load(f)["serving"]
    return t, serving


def _wait(reqs, dev, timeout=120.0):
    end = time.monotonic() + timeout
    for r in reqs:
        r.result(timeout=max(0.1, end - time.monotonic()))
    return len(dev.programs)


def _prompt(index, n):
    return [(index * 13 + i * 7) % 89 + 2 for i in range(n)]


def _rehearse(traffic, config, launches):
    """Warm-up waves, then the backlog: every client's first request (the
    ramp, which the benchmark keeps inside its set-up), then ``launches``
    launches of the steady mix, each finished request replaced at once.
    Returns ``(waves, ramp, stretch)``: the ``(Q, T, R)`` of the launches
    of each."""
    from benchmark.lib import traffic as T
    t, serving = _load(traffic, config)
    slots = int(t["slots"])
    # every slot may grow to max_len: the pool never preempts here
    pool = mock_pool(slots=slots, max_len=int(serving["max_len"]),
                     block_size=int(serving["block_size"]),
                     num_blocks=slots * int(serving["max_len"])
                     // int(serving["block_size"]))
    dev = RaggedMockDevice(pool, int(serving["prefill_budget"]))
    sched = dev.scheduler(max_queue=int(serving["max_queue"]))
    try:
        n = 0
        for wave in t["warmup"]:
            # submitted together (lib/serve.py:_warm): the scheduler is
            # held in its first admission until the wave is in the queue
            dev.prefill_gate.clear()
            reqs = []
            for count, plen, max_tokens in wave:
                for _ in range(count):
                    n += 1
                    reqs.append(sched.submit(GenerationRequest(
                        _prompt(n, plen), max_tokens)))
            dev.prefill_gate.set()
            _wait(reqs, dev)
        waves = len(dev.programs)

        clients = int(t["clients"])
        plan = T.backlog_plan(t)
        live, nxt = {}, [0] * clients
        stop_at = [None]

        def submit(c):
            index, p, o = T.backlog_request(plan, clients, c, nxt[c])
            nxt[c] += 1
            live[c] = sched.submit(GenerationRequest(_prompt(index, p), o))

        # a finished request is replaced before the next launch is
        # planned: the step hook runs on the scheduler's thread
        step = dev.do_step

        def do_step(slot_requests, plan_, prev=None):
            for c, r in list(live.items()):
                if r.done() and (stop_at[0] is None
                                 or len(dev.programs) < stop_at[0]):
                    submit(c)
            return step(slot_requests, plan_, prev)

        sched._do_chunked = do_step
        dev.prefill_gate.clear()
        for c in range(clients):
            submit(c)
        dev.prefill_gate.set()
        # the ramp ends when every client has its first token
        end = time.monotonic() + 120.0
        while any(not r.tokens and not r.done() for r in live.values()):
            assert time.monotonic() < end, "the ramp did not end"
            time.sleep(0.002)
        ramp = len(dev.programs)
        stop_at[0] = ramp + launches
        while len(dev.programs) < stop_at[0]:
            assert time.monotonic() < end + 120.0, "the stretch stalled"
            time.sleep(0.002)
        progs = list(dev.programs)
        return progs[:waves], progs[waves:ramp], progs[ramp:stop_at[0]]
    finally:
        sched.close(cancel_pending=True)


@pytest.mark.parametrize("traffic,config", CELLS,
                         ids=[c for _, c in CELLS])
def test_the_waves_reach_every_program_of_the_steady_mix(traffic, config):
    waves, ramp, stretch = _rehearse(traffic, config, launches=700)
    warmed = {(q, t) for q, t, _ in waves}
    met = {(q, t) for q, t, _ in stretch}
    assert met and met <= warmed, (sorted(met - warmed), sorted(warmed))
    # one R a Q: the tower's rows are no bucket of their own
    rows = {}
    for q, _, r in waves + ramp + stretch:
        assert rows.setdefault(q, r) == r and r <= q
    # the steady mix runs on its own axis wherever decode rows are in it
    assert any(r < q for q, _, r in stretch)
