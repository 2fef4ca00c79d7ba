"""Training loop: model FLOP/s utilisation, %: 6 N tokens/s over the
bf16 peak, N counted from the configuration, recomputation and
attention's sequence term not counted. An end-to-end utilisation over
the whole window, not a kernel's share."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import peaks as P


def read(r):
    if "steps" not in r:
        return None
    m = r["model"]
    n = K.gpt_param_count(int(m["vocab_size"]), int(m["hidden_size"]),
                          int(m["num_hidden_layers"]),
                          int(m["intermediate_size"]),
                          int(m["max_position_embeddings"]))
    tok_s = r["steps"] * r["batch"] * r["seq"] / r["elapsed_s"]
    return 100.0 * K.train_flops_per_token(n) * tok_s \
        / P.peaks_for(r["device_kind"])["bf16_flops_per_s"]
