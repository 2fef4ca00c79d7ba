"""Percentiles, the cap for a missing request, kernel costs, peaks."""
import json
import os

import pytest

from benchmark.lib import kernel_costs as K
from benchmark.lib import peaks as P
from benchmark.lib import stats as S


def test_percentile_interpolates_like_numpy():
    import numpy as np
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 25, 50, 95, 100):
        assert S.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert S.percentile([4.0], 95) == 4.0
    with pytest.raises(ValueError):
        S.percentile([], 95)


def test_a_missing_request_enters_at_the_cap_and_only_raises_the_tail():
    served = [10.0] * 19
    assert S.percentile(S.with_missing(served + [None], 5000.0), 95) > 10.0
    assert S.with_missing([None, float("inf"), 7.0, 9e9], 100.0) == \
        [100.0, 100.0, 7.0, 100.0]


def test_iqr_share_is_the_contracts_spread():
    import statistics
    xs = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q = statistics.quantiles(xs, n=4)
    assert S.iqr_share(xs) == pytest.approx((q[2] - q[0]) / statistics.median(xs))


def test_gpt2_parameter_counts():
    # GPT-2 124M with the vocabulary padded to 50304 (tied head)
    assert K.gpt_param_count(50304, 768, 12, 3072, 1024) == 124_475_904
    assert K.gpt_param_count(50257, 768, 12, 3072, 1024) == 124_439_808
    assert K.gpt_param_count(50257, 1280, 36, 5120, 1024) == 774_030_080


def test_flash_flops_by_hand():
    # one layer, batch 1, 1 head, seq 4, head_dim 2: a [4,4,2] product is
    # 2*4*4*2 = 64 FLOPs, causal half 32; six products a step
    assert K.causal_attention_flops(1, 1, 4, 2, 1) == 32.0
    assert K.flash_train_flops(1, 1, 4, 2, layers=1) == 6 * 32.0
    assert K.flash_train_flops(8, 12, 1024, 64, 12) == \
        12 * 6 * 2.0 * 8 * 12 * 1024 * 1024 * 64 / 2


def test_paged_attention_bytes_by_hand():
    # gpt2-large: 36 layers x 2 x 20 heads x 64 x 2 B = 184,320 B a token
    assert K.kv_bytes_per_token(36, 20, 64, 2) == 184_320
    # 100 live blocks, 10 active rows: 90 blocks x 16 tokens counted
    assert K.paged_attention_read_bytes(100, 10, 16, 36, 20, 64, 2) == \
        90 * 16 * 184_320
    assert K.paged_attention_read_bytes(3, 10, 16, 36, 20, 64, 2) == 0.0


def test_peaks_table_refuses_an_unlisted_device():
    v5e = P.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        P.peaks_for("cpu")


def test_benchmark_json_names_only_files_that_exist():
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(root, c["file"]))
    for w in bench["workloads"]:
        t = json.load(open(os.path.join(root, "benchmark", "traffic",
                                        w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(root, "benchmark", "drivers",
                                           t["driver"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(root, "benchmark", "layer_metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
