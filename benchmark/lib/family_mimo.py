"""The ``mimo`` family's adapter: everything about serving MiMo-V2-Flash
that differs from the other families — build the model, make its weights,
size its pool, run its reference — in ONE module, chosen by the
configuration file's ``"family"`` key (``lib/serve_family.py``).

It goes through what a user calls (``MiMoV2ForCausalLM(cfg, dtype,
param_init)``; the engine shares the pool's bytes out between the cache
groups itself) and takes its weights from ``lib/weights_mimo.py``.
"""
from __future__ import annotations

import numpy as np

from . import traffic as T
from . import weights_mimo as W

# jax.named_scope names of the program whose device time a per-layer
# metric reads (layer_metrics/moe_step_ms.py)
SCOPES = ("moe_experts",)


class Weights:
    """One seed's weights, made a piece at a time and never kept: what
    ``reference_mimo`` calls ``make``."""

    def __init__(self, seed: int, model: dict, dtype: str):
        self.seed, self.model, self.dtype = int(seed), model, dtype

    def embed(self):
        return W.embed(self.seed, self.model, self.dtype)

    def layer(self, i: int) -> dict:
        return W.layer_leaves(self.seed, i, self.model, self.dtype)

    def final_norm(self):
        return W.final_norm(self.seed, self.model, self.dtype)

    def head(self):
        return W.head(self.seed, self.model, self.dtype)


def program_config(model: dict):
    from paddle_tpu.models.mimo import MiMoV2Config
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "v_head_dim", "swa_num_attention_heads",
            "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
            "hybrid_layer_pattern", "sliding_window", "rope_theta",
            "swa_rope_theta", "partial_rotary_factor",
            "attention_value_scale", "n_routed_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "layernorm_epsilon", "max_position_embeddings")
    L = int(model["num_hidden_layers"])
    return MiMoV2Config(
        **{k: model[k] for k in keys},
        moe_layer_freq=[int(W.is_routed(model, i)) for i in range(L)],
        experts_held=W.held_range(model))


def build_lm(model: dict, seed: int, dtype: str):
    """``MiMoV2ForCausalLM`` at the configuration's sizes holding the
    benchmark's seeded weights: every parameter is made once, in its
    serving dtype, by the model's own ``param_init`` hook."""
    from paddle_tpu.models.mimo import MiMoV2ForCausalLM
    make = Weights(seed, model, dtype)
    current = {"index": None, "leaves": None}

    def param_init(name, shape, dt):
        if name == "embed":
            return make.embed()
        if name == "norm":
            return make.final_norm()
        if name == "lm_head":
            return make.head()
        _, index, *rest = name.split(".")
        if current["index"] != int(index):      # layers come in order
            if current["leaves"]:
                raise RuntimeError(
                    f"weight leaves layer {current['index']} did not take: "
                    f"{sorted(current['leaves'])}")
            current.update(index=int(index), leaves=make.layer(int(index)))
        return current["leaves"].pop(rest[-1])

    net = MiMoV2ForCausalLM(program_config(model), dtype=dtype,
                            param_init=param_init)
    if current["leaves"]:
        raise RuntimeError(f"weight leaves the program did not take: "
                           f"{sorted(current['leaves'])}")
    return net


def stored_block_bytes(model: dict, serving: dict) -> int:
    """Bytes of one block of ``block_size`` tokens held in EVERY layer, as
    the pool stores a row (``[K | V]`` of every KV head in whole 128-lane
    tiles, ``paddle_tpu.models.mimo.stored_lanes``)."""
    import jax.numpy as jnp
    from paddle_tpu.models.mimo import stored_lanes
    total = 0
    for i in range(int(model["num_hidden_layers"])):
        _, hkv, dk, dv = W.attention_dims(model, W.is_window(model, i))
        total += hkv * stored_lanes(dk, dv)
    return total * int(serving["block_size"]) \
        * jnp.dtype(serving["dtype"]).itemsize


def pool_blocks_for_share(model: dict, serving: dict) -> int:
    """The configuration's pool rule: the blocks a cache held UNIFORMLY in
    every layer would have in ``pool_hbm_share`` of the device memory
    still free once the weights are resident. It is the engine that
    shares those bytes out between its cache groups
    (``serving/engine.py:_group_block_counts``): the window layers get
    what 128 slots and a chunk can hold at all, the global layers the
    rest."""
    import jax
    if "pool_blocks" in serving:        # the CPU rehearsals: no memory_stats
        return int(serving["pool_blocks"])
    ms = jax.devices()[0].memory_stats() or {}
    free = ms["bytes_limit"] - ms["bytes_in_use"]
    return max(0, int(free * float(serving["pool_hbm_share"]))
               // stored_block_bytes(model, serving) - 1)


def served_gaps(config: dict, sample: list, seed: int, weight_seed: int,
                quant=None) -> dict:
    """Normalised gaps of every served token of ``sample`` through
    ``reference_mimo.served_margins``, layer by layer, in blocks of
    ``rows_per_call`` sequences of ``width`` positions. With ``quant``
    also the control's gaps."""
    from . import reference_mimo as R
    model, check = config["model"], config["serving"]["check"]
    vocab, width = int(model["vocab_size"]), int(check["width"])
    r = int(check["rows_per_call"])
    B = -(-len(sample) // r) * r
    n_pad = -(-max(len(x["tokens"]) for x in sample) // 64) * 64
    ids = np.zeros((B, width), np.int32)
    pos = np.zeros((B, n_pad), np.int32)
    served = np.zeros((B, n_pad), np.int32)
    valid = np.zeros((B, n_pad), bool)
    for b, x in enumerate(sample):
        prompt = T.prompt_tokens(seed, x["index"], x["prompt_len"], vocab)
        text = prompt + x["tokens"]
        if len(text) > width:
            raise ValueError(f"request {x['index']}: {len(text)} tokens "
                             f"exceed the reference width {width}")
        n = len(x["tokens"])
        ids[b, :len(text)] = text
        pos[b, :n] = len(prompt) - 1 + np.arange(n)
        served[b, :n] = x["tokens"]
        valid[b, :n] = True
    out = R.served_margins(
        Weights(weight_seed, model, config["serving"]["dtype"]), model, ids,
        pos, served, rows_per_call=r, quant=quant,
        q_block=check.get("q_block"), cap_share=check.get("cap_share"))
    res = {"gaps": (out["gap"] / out["std"])[valid]}
    if quant is not None:
        res["control_gaps"] = (out["control_gap"] / out["std"])[valid]
    return res
