"""The ``longcat`` family's adapter: everything about serving
LongCat-Flash that differs from GPT-2 — build the model, make its
weights, size its pool, run its reference — in ONE module, chosen by the
configuration file's ``"family"`` key (``lib/serve_family.py``).

Beside ``lib/system.py`` and the other ``family_*`` modules the only file
under ``benchmark/`` that imports ``paddle_tpu``; it goes through what a
user calls (``LongCatForCausalLM(cfg, dtype, param_init)``,
``PagedKVPool``'s sizing rule) and takes its weights from
``lib/weights_longcat.py``, a SUB-BLOCK at a time.
"""
from __future__ import annotations

import numpy as np

from . import traffic as T
from . import weights_longcat as W


# jax.named_scope names of the program whose instructions a per-layer
# metric looks up in the compiled steps (layer_metrics/moe_step_ms.py;
# section_ms.shortcut.py: whether the program names the section at all)
SCOPES = ("moe_experts", "shortcut")


class Weights:
    """One seed's weights, made a piece at a time and never kept: what
    ``reference_longcat`` calls ``make`` (``layer(i)``: sub-block ``i``)."""

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
    """``LongCatConfig`` of the ``model`` group: ``num_hidden_layers`` is
    the published layers held (two sub-blocks each), ``n_routed_experts``
    the ROUTER's width (the program counts the experts with weights)."""
    from paddle_tpu.models.longcat import LongCatConfig
    keys = ("vocab_size", "hidden_size", "ffn_hidden_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "mla_scale_q_lora", "mla_scale_kv_lora", "zero_expert_num",
            "moe_topk", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta", "max_position_embeddings")
    return LongCatConfig(
        **{k: model[k] for k in keys},
        num_layers=int(model["num_hidden_layers"]),
        expert_ffn_hidden_size=int(model["moe_intermediate_size"]),
        n_routed_experts=W.real_experts(model),
        experts_held=W.held_range(model))


def build_lm(model: dict, seed: int, dtype: str):
    """``LongCatForCausalLM`` at the configuration's sizes holding the
    benchmark's seeded weights: every parameter is made once, in its
    serving dtype, by the model's own ``param_init`` hook, a sub-block's
    leaves at a time."""
    from paddle_tpu.models.longcat import LongCatForCausalLM
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
        if current["index"] != int(index):      # sub-blocks come in order
            if current["leaves"]:
                raise RuntimeError(
                    f"weight leaves sub-block {current['index']} did not "
                    f"take: {sorted(current['leaves'])}")
            current.update(index=int(index), leaves=make.layer(int(index)))
        return current["leaves"].pop(rest[-1])

    net = LongCatForCausalLM(program_config(model), dtype=dtype,
                             param_init=param_init)
    if current["leaves"]:
        raise RuntimeError(f"weight leaves the program did not take: "
                           f"{sorted(current['leaves'])}")
    return net


def latent_lanes(model: dict) -> int:
    return program_config(model).latent_lanes


def pool_blocks_for_share(model: dict, serving: dict) -> int:
    """The configuration's pool rule for a latent cache: blocks that take
    ``pool_hbm_share`` of the device memory still free once the weights
    are resident, a block being ``attention_layers x block_size`` rows of
    ``latent_lanes`` values (every SUB-BLOCK holds a cache)."""
    import jax
    from paddle_tpu.serving import PagedKVPool
    if "pool_blocks" in serving:        # the CPU rehearsals: no memory_stats
        return int(serving["pool_blocks"])
    ms = jax.devices()[0].memory_stats() or {}
    free = ms["bytes_limit"] - ms["bytes_in_use"]
    return PagedKVPool.blocks_within_budget(
        int(free * float(serving["pool_hbm_share"])),
        num_layers=W.sub_blocks(model), num_heads=1,
        block_size=int(serving["block_size"]), lanes=latent_lanes(model),
        dtype=serving["dtype"])


def served_gaps(config: dict, sample: list, seed: int, weight_seed: int,
                quant=None) -> dict:
    """Normalised gaps of every served token of ``sample`` through
    ``reference_longcat.served_margins``, sub-block by sub-block, in blocks
    of ``rows_per_call`` sequences of ``width`` positions. With ``quant``
    also the control's gaps."""
    from . import reference_longcat as R
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
