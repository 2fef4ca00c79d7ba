"""The ``nemotron_h`` family's adapter: everything about serving
Nemotron-H that differs from the other families — build the model, make
its weights, size its pool, run its reference — in ONE module, chosen by
the configuration file's ``"family"`` key (``lib/serve_family.py``).

It goes through what a user calls (``NemotronHForCausalLM(cfg, dtype,
param_init)``; the engine sizes the slots' recurrent state from the
model's decoder spec and ``num_slots`` itself) and takes its weights from
``lib/weights_nemotron_h.py``, a BLOCK at a time.
"""
from __future__ import annotations

import numpy as np

from . import traffic as T
from . import weights_nemotron_h as W

# jax.named_scope names of the program whose instructions a per-layer
# metric looks up in the compiled steps (layer_metrics/moe_step_ms.py)
SCOPES = ("moe_experts",)


class Weights:
    """One seed's weights, made a piece at a time and never kept: what
    ``reference_nemotron_h`` calls ``make`` (``layer(i)``: block ``i``)."""

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
    """``NemotronHConfig`` of the ``model`` group, under the source's key
    names: ``n_routed_experts`` is the ROUTER's width, ``experts_held`` the
    range this chip holds, ``hybrid_override_pattern`` the published
    pattern (the program takes its first ``num_hidden_layers`` blocks)."""
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "hybrid_override_pattern", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
            "chunk_size", "use_conv_bias", "n_routed_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "moe_latent_size", "moe_shared_expert_intermediate_size",
            "n_shared_experts", "routed_scaling_factor", "norm_topk_prob",
            "layer_norm_epsilon", "max_position_embeddings")
    return NemotronHConfig(**{k: model[k] for k in keys},
                           experts_held=W.held_range(model))


def build_lm(model: dict, seed: int, dtype: str):
    """``NemotronHForCausalLM`` at the configuration's sizes holding the
    benchmark's seeded weights: every parameter is made once, in its
    serving dtype, by the model's own ``param_init`` hook, a block's
    leaves at a time."""
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM
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
        if current["index"] != int(index):      # blocks come in order
            if current["leaves"]:
                raise RuntimeError(
                    f"weight leaves block {current['index']} did not take: "
                    f"{sorted(current['leaves'])}")
            current.update(index=int(index), leaves=make.layer(int(index)))
        return current["leaves"].pop(rest[-1])

    net = NemotronHForCausalLM(program_config(model), dtype=dtype,
                               param_init=param_init)
    if current["leaves"]:
        raise RuntimeError(f"weight leaves the program did not take: "
                           f"{sorted(current['leaves'])}")
    return net


def state_layers(model: dict) -> int:
    """Blocks that hold a recurrent state: the ``M`` blocks held."""
    return W.pattern(model).count(W.MAMBA)


def cache_layers(model: dict) -> int:
    """Blocks that hold a KV cache: the ``*`` blocks held."""
    return W.pattern(model).count(W.ATTENTION)


def state_bytes_per_slot(model: dict) -> int:
    """Bytes ONE slot's recurrent state takes over the served blocks: the
    program's own state descriptor (the convolution's tail and the
    recurrence's state, float32) times the ``M`` blocks."""
    return program_config(model).state_spec.nbytes * state_layers(model)


def pool_blocks_for_share(model: dict, serving: dict) -> int:
    """The configuration's pool rule: first the slots' recurrent state
    (``state_slots + 1`` rows: the engine's array has one no slot owns),
    then blocks that take ``pool_hbm_share`` of the device memory still
    free beside the weights and that state — at most ``pool_blocks_max``,
    what every slot at ``max_len`` needs with the chunk budget's spare
    (one cache layer of 1 KB a token: the share alone would buy blocks for
    a hundred times the slots' contexts) — a block being ``* blocks x KV
    heads x block_size`` rows of ``2 x head_dim`` values."""
    import jax
    from paddle_tpu.serving import PagedKVPool
    if "pool_blocks" in serving:        # the CPU rehearsals: no memory_stats
        return int(serving["pool_blocks"])
    ms = jax.devices()[0].memory_stats() or {}
    free = ms["bytes_limit"] - ms["bytes_in_use"] \
        - (int(serving["state_slots"]) + 1) * state_bytes_per_slot(model)
    by_share = PagedKVPool.blocks_within_budget(
        int(free * float(serving["pool_hbm_share"])),
        num_layers=cache_layers(model),
        num_heads=int(model["num_key_value_heads"]),
        block_size=int(serving["block_size"]),
        head_dim=int(model["head_dim"]), dtype=serving["dtype"])
    return min(int(serving["pool_blocks_max"]), by_share)


def served_gaps(config: dict, sample: list, seed: int, weight_seed: int,
                quant=None) -> dict:
    """Normalised gaps of every served token of ``sample`` through
    ``reference_nemotron_h.served_margins``, block by block, in groups of
    ``rows_per_call`` sequences of ``width`` positions. With ``quant``
    (``"int8"``: W8A8 linears; ``"bf16_state"``: the recurrent state
    rounded to bfloat16 after every step) also the control's gaps."""
    from . import reference_nemotron_h as R
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
        q_block=check.get("q_block"))
    res = {"gaps": (out["gap"] / out["std"])[valid]}
    if quant is not None:
        res["control_gaps"] = (out["control_gap"] / out["std"])[valid]
    return res
