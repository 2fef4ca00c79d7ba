"""The ``falcon_h1`` family's adapter: everything about serving Falcon-H1
that differs from the other families — build the model, make its weights,
size its pool, run its reference — in ONE module, chosen by the
configuration file's ``"family"`` key (``lib/serve_family.py``).

It goes through what a user calls (``FalconH1ForCausalLM(cfg, dtype,
param_init)``; the engine sizes the slots' recurrent state from the
model's decoder spec and ``num_slots`` itself) and takes its weights from
``lib/weights_falcon_h1.py``.
"""
from __future__ import annotations

import numpy as np

from . import traffic as T
from . import weights_falcon_h1 as W

# the mixer's device time is read by SECTION (lib/launch_trace.py), not by
# a scope's instructions: nothing for lib/scope_ops.py to look up
SCOPES = ()


class Weights:
    """One seed's weights, made a piece at a time and never kept: what
    ``reference_falcon_h1`` calls ``make``."""

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
    from paddle_tpu.models.falcon_h1 import FalconH1Config
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
            "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias",
            "mamba_rms_norm", "mamba_norm_before_gate", "attention_bias",
            "mlp_bias", "rms_norm_eps", "rope_theta",
            "max_position_embeddings", "embedding_multiplier",
            "lm_head_multiplier", "attention_in_multiplier",
            "attention_out_multiplier", "key_multiplier",
            "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
            "mlp_multipliers")
    return FalconH1Config(**{k: model[k] for k in keys})


def build_lm(model: dict, seed: int, dtype: str):
    """``FalconH1ForCausalLM`` at the configuration's sizes holding the
    benchmark's seeded weights: every parameter is made once, in its
    serving dtype, by the model's own ``param_init`` hook."""
    from paddle_tpu.models.falcon_h1 import FalconH1ForCausalLM
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

    net = FalconH1ForCausalLM(program_config(model), dtype=dtype,
                              param_init=param_init)
    if current["leaves"]:
        raise RuntimeError(f"weight leaves the program did not take: "
                           f"{sorted(current['leaves'])}")
    return net


def state_bytes_per_slot(model: dict) -> int:
    """Bytes ONE slot's recurrent state takes over the served layers: the
    program's own state descriptor (the convolution's tail and the
    recurrence's state, float32) times the layers."""
    return program_config(model).state_spec.nbytes \
        * int(model["num_hidden_layers"])


def pool_blocks_for_share(model: dict, serving: dict) -> int:
    """The configuration's pool rule: first the slots' recurrent state
    (``state_slots + 1`` rows: the engine's array has one no slot owns),
    then blocks that take ``pool_hbm_share`` of the device memory still
    free beside the weights and that state, a block being ``layers x KV
    heads x block_size`` rows of ``2 x head_dim`` values."""
    import jax
    from paddle_tpu.serving import PagedKVPool
    if "pool_blocks" in serving:        # the CPU rehearsals: no memory_stats
        return int(serving["pool_blocks"])
    ms = jax.devices()[0].memory_stats() or {}
    free = ms["bytes_limit"] - ms["bytes_in_use"] \
        - (int(serving["state_slots"]) + 1) * state_bytes_per_slot(model)
    return PagedKVPool.blocks_within_budget(
        int(free * float(serving["pool_hbm_share"])),
        num_layers=int(model["num_hidden_layers"]),
        num_heads=int(model["num_key_value_heads"]),
        block_size=int(serving["block_size"]),
        head_dim=int(model["head_dim"]), dtype=serving["dtype"])


def served_gaps(config: dict, sample: list, seed: int, weight_seed: int,
                quant=None) -> dict:
    """Normalised gaps of every served token of ``sample`` through
    ``reference_falcon_h1.served_margins``, layer by layer, in blocks of
    ``rows_per_call`` sequences of ``width`` positions. With ``quant``
    (``"int8"``: W8A8 linears; ``"bf16_state"``: the recurrent state
    rounded to bfloat16 after every step) also the control's gaps."""
    from . import reference_falcon_h1 as R
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
        pos, served, rows_per_call=r, quant=quant)
    res = {"gaps": (out["gap"] / out["std"])[valid]}
    if quant is not None:
        res["control_gaps"] = (out["control_gap"] / out["std"])[valid]
    return res
