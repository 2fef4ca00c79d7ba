"""The ``lfm2`` family's adapter: everything about serving LFM2-MoE that
differs from the other families — build the model, make its weights, size
its pool, run its reference — in ONE module, chosen by the configuration
file's ``"family"`` key (``lib/serve_family.py``).

It goes through what a user calls (``Lfm2MoeForCausalLM(cfg, dtype,
param_init)``; the engine sizes the block arrays from the layers that
hold a cache and the slots' convolution tails from the others itself)
and takes its weights from ``lib/weights_lfm2.py``.
"""
from __future__ import annotations

import numpy as np

from . import traffic as T
from . import weights_lfm2 as W

# jax.named_scope names of the program whose device time a per-layer
# metric reads by a scope's instructions (layer_metrics/moe_step_ms.py);
# the conv operator's time is read by SECTION (lib/launch_trace.py)
SCOPES = ("moe_experts",)


class Weights:
    """One seed's weights, made a piece at a time and never kept: what
    ``reference_lfm2`` calls ``make``. The head is the embedding."""

    def __init__(self, seed: int, model: dict, dtype: str):
        self.seed, self.model, self.dtype = int(seed), model, dtype

    def embed(self):
        return W.embed(self.seed, self.model, self.dtype)

    def layer(self, i: int) -> dict:
        return W.layer_leaves(self.seed, i, self.model, self.dtype)

    def final_norm(self):
        return W.final_norm(self.seed, self.model, self.dtype)


def program_config(model: dict):
    from paddle_tpu.models.lfm2 import Lfm2MoeConfig
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads", "conv_L_cache",
            "conv_bias", "num_dense_layers", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
            "routed_scaling_factor", "norm_eps", "rope_theta",
            "max_position_embeddings")
    return Lfm2MoeConfig(**{k: model[k] for k in keys},
                         experts_held=W.held_range(model))


def build_lm(model: dict, seed: int, dtype: str):
    """``Lfm2MoeForCausalLM`` at the configuration's sizes holding the
    benchmark's seeded weights: every parameter is made once, in its
    serving dtype, by the model's own ``param_init`` hook."""
    from paddle_tpu.models.lfm2 import Lfm2MoeForCausalLM
    make = Weights(seed, model, dtype)
    current = {"index": None, "leaves": None}

    def param_init(name, shape, dt):
        if name == "embed":
            return make.embed()
        if name == "embedding_norm":
            return make.final_norm()
        _, index, *rest = name.split(".")
        if current["index"] != int(index):      # layers come in order
            if current["leaves"]:
                raise RuntimeError(
                    f"weight leaves layer {current['index']} did not take: "
                    f"{sorted(current['leaves'])}")
            current.update(index=int(index), leaves=make.layer(int(index)))
        return current["leaves"].pop(rest[-1])

    net = Lfm2MoeForCausalLM(program_config(model), dtype=dtype,
                             param_init=param_init)
    if current["leaves"]:
        raise RuntimeError(f"weight leaves the program did not take: "
                           f"{sorted(current['leaves'])}")
    return net


def cache_layers(model: dict) -> int:
    """The served layers that hold a KV cache: the ``full_attention``
    ones."""
    L = int(model["num_hidden_layers"])
    return sum(1 for i in range(L) if not W.is_conv(model, i))


def state_bytes_per_slot(model: dict) -> int:
    """Bytes ONE slot's convolution tails take over the served ``conv``
    layers: the program's own state descriptor times those layers."""
    return program_config(model).state_spec.nbytes \
        * (int(model["num_hidden_layers"]) - cache_layers(model))


def pool_blocks_for_share(model: dict, serving: dict) -> int:
    """The configuration's pool rule: first the slots' convolution tails
    (``state_slots + 1`` rows: the engine's array has one no slot owns),
    then blocks that take ``pool_hbm_share`` of the device memory still
    free beside the weights and that state, a block being ``attention
    layers x KV heads x block_size`` rows of ``2 x head_dim`` values — the
    ``conv`` layers hold none."""
    import jax
    from paddle_tpu.serving import PagedKVPool
    if "pool_blocks" in serving:        # the CPU rehearsals: no memory_stats
        return int(serving["pool_blocks"])
    ms = jax.devices()[0].memory_stats() or {}
    free = ms["bytes_limit"] - ms["bytes_in_use"] \
        - (int(serving["state_slots"]) + 1) * state_bytes_per_slot(model)
    return PagedKVPool.blocks_within_budget(
        int(free * float(serving["pool_hbm_share"])),
        num_layers=cache_layers(model),
        num_heads=int(model["num_key_value_heads"]),
        block_size=int(serving["block_size"]),
        head_dim=W.head_dim(model), dtype=serving["dtype"])


def served_gaps(config: dict, sample: list, seed: int, weight_seed: int,
                quant=None) -> dict:
    """Normalised gaps of every served token of ``sample`` through
    ``reference_lfm2.served_margins``, layer by layer, in blocks of
    ``rows_per_call`` sequences of ``width`` positions. With ``quant``
    also the control's gaps."""
    from . import reference_lfm2 as R
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
