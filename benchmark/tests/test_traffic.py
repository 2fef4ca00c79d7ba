"""The general traffic generator: same work for every seed, in another
order; due times on the generator's own clock."""
from benchmark.lib import traffic as T

MIX = {"rate_per_s": 10.0, "lead_s": 2.0, "prompt_tokens": [32, 512],
       "output_tokens": [16, 128]}


def test_same_seed_same_schedule_and_prompts():
    a = T.open_loop_schedule(MIX, 2 ** 31 + 7, 20.0)
    assert a == T.open_loop_schedule(MIX, 2 ** 31 + 7, 20.0)
    assert T.prompt_tokens(2 ** 31 + 7, 3, 40, 50257) == \
        T.prompt_tokens(2 ** 31 + 7, 3, 40, 50257)
    assert T.prompt_tokens(1, 3, 40, 50257) != T.prompt_tokens(2, 3, 40, 50257)
    toks = T.prompt_tokens(5, 0, 1000, 50257)
    assert min(toks) >= 1 and max(toks) < 50257


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    a = T.open_loop_schedule(MIX, 1, 20.0)
    b = T.open_loop_schedule(MIX, 2, 20.0)
    assert len(a) == len(b) == 220                  # rate x (lead + seconds)
    assert a != b
    assert sorted(p for _, p, _ in a) == sorted(p for _, p, _ in b)
    assert sorted(o for _, _, o in a) == sorted(o for _, _, o in b)
    assert min(p for _, p, _ in a) >= 32 and max(p for _, p, _ in a) <= 512
    assert min(o for _, _, o in a) >= 16 and max(o for _, _, o in a) <= 128
    gaps = lambda s: sorted(round(y[0] - x[0], 9) for x, y in zip(s, s[1:]))
    # all gaps but the first are the same multiset (the first is halved)
    assert abs(a[-1][0] - b[-1][0]) < 0.5
    assert all(0 <= t <= 22.0 for t, _, _ in a)
    assert [t for t, _, _ in a] == sorted(t for t, _, _ in a)


def test_exponential_gaps_sum_to_the_window():
    g = T.exponential_gaps(8.0, 400)
    assert abs(g.sum() - 50.0) < 1e-9 and (g > 0).all()


def test_backlog_clients_walk_one_pool_and_stagger_their_first_request():
    spec = {"clients": 4, "pool": 8, "prompt_tokens": [128, 512],
            "output_tokens": [128, 512], "plan_seed": 3}
    plan = T.backlog_plan(spec)
    assert plan == T.backlog_plan(spec)          # the run's seed plays no part
    assert plan != T.backlog_plan(dict(spec, plan_seed=4))
    first = [T.backlog_request(plan, 4, c, 0) for c in range(4)]
    later = [T.backlog_request(plan, 4, c, 1) for c in range(4)]
    assert [i for i, _, _ in first] == [0, 1, 2, 3]
    assert [i for i, _, _ in later] == [4, 5, 6, 7]
    full = [plan["pool"][i][1] for i in range(4)]
    assert all(1 <= o <= f for (_, _, o), f in zip(first, full))
    assert sorted(plan["stagger"]) == [0.125, 0.375, 0.625, 0.875]
    assert all(p + o <= 1024 for p, o in plan["pool"])
