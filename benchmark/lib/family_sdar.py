"""The ``sdar`` family's adapter: everything about serving SDAR-MoE that
differs from the other families — build the model, make its weights, size
its pool, run its reference — in ONE module, chosen by the configuration
file's ``"family"`` key (``lib/serve_family.py``).

It goes through what a user calls (``SDARForCausalLM(cfg, dtype,
param_init)``, ``PagedKVPool``'s sizing rule) and takes its weights from
``lib/weights_sdar.py``.

The check is teacher-forced on the states the program saw
(``lib/reference_sdar.py``), so a sampled record must hold, beside its
tokens, the pass of its block in which each was fixed: ``record["passes"]``,
which ``drivers/serve_backlog_blocks.py`` joins on from the engine's
retired traces.
"""
from __future__ import annotations

import numpy as np

from . import traffic as T
from . import weights_sdar as W

# jax.named_scope names of the program whose device time a per-layer
# metric reads (layer_metrics/moe_step_ms.py, unmask_step_ms.py)
SCOPES = ("moe_experts", "unmask")


class Weights:
    """One seed's weights, made a piece at a time and never kept: what
    ``reference_sdar`` calls ``make``."""

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
    from paddle_tpu.models.sdar import SDARConfig
    keys = ("vocab_size", "hidden_size", "moe_intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_experts_per_tok", "norm_topk_prob",
            "rms_norm_eps", "rope_theta", "max_position_embeddings",
            "block_length", "denoising_steps", "mask_token_id")
    return SDARConfig(**{k: model[k] for k in keys},
                      num_experts=int(model["n_routed_experts"]),
                      experts_held=W.held_range(model))


def build_lm(model: dict, seed: int, dtype: str):
    """``SDARForCausalLM`` at the configuration's sizes holding the
    benchmark's seeded weights: every parameter is made once, in its
    serving dtype, by the model's own ``param_init`` hook."""
    from paddle_tpu.models.sdar import SDARForCausalLM
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
            current.update(index=int(index), leaves=make.layer(int(index)))
        return current["leaves"].pop(rest[-1])

    net = SDARForCausalLM(program_config(model), dtype=dtype,
                          param_init=param_init)
    if current["leaves"]:
        raise RuntimeError(f"weight leaves the program did not take: "
                           f"{sorted(current['leaves'])}")
    return net


def pool_blocks_for_share(model: dict, serving: dict) -> int:
    """The configuration's pool rule: blocks that take ``pool_hbm_share``
    of the device memory still free once the weights are resident, a
    block being ``layers x KV heads x block_size`` rows of ``2 x
    head_dim`` values."""
    import jax
    from paddle_tpu.serving import PagedKVPool
    if "pool_blocks" in serving:        # the CPU rehearsals: no memory_stats
        return int(serving["pool_blocks"])
    ms = jax.devices()[0].memory_stats() or {}
    free = ms["bytes_limit"] - ms["bytes_in_use"]
    return PagedKVPool.blocks_within_budget(
        int(free * float(serving["pool_hbm_share"])),
        num_layers=int(model["num_hidden_layers"]),
        num_heads=int(model["num_key_value_heads"]),
        block_size=int(serving["block_size"]),
        head_dim=int(model["head_dim"]), dtype=serving["dtype"])


def served_gaps(config: dict, sample: list, seed: int, weight_seed: int,
                quant=None) -> dict:
    """Through ``reference_sdar.served_margins``: ``gaps`` (per served
    token, normalised by its row's logit spread) and ``order_gaps`` (per
    pass) of ``sample``, whose records hold ``passes``. With ``quant``
    also the control's."""
    from . import reference_sdar as R
    model, check = config["model"], config["serving"]["check"]
    vocab = int(model["vocab_size"])
    requests = []
    for x in sample:
        if len(x.get("passes", ())) != len(x["tokens"]):
            raise ValueError(
                f"request {x['index']}: the record holds no pass for each "
                f"of its {len(x['tokens'])} tokens — the driver did not "
                f"find the engine's trace of it")
        requests.append((T.prompt_tokens(seed, x["index"], x["prompt_len"],
                                         vocab), x["tokens"], x["passes"]))
    out = R.served_margins(
        Weights(weight_seed, model, config["serving"]["dtype"]), model,
        requests, width=int(check["width"]), states=int(check["states"]),
        quant=quant, q_block=check.get("q_block"),
        cap_share=check.get("cap_share"),
        states_per_call=int(check.get("states_per_call", 64)))
    res = {"gaps": out["gap"] / out["std"], "order_gaps": out["order_gap"]}
    if quant is not None:
        res["control_gaps"] = out["control_gap"] / out["std"]
        res["control_order_gaps"] = out["control_order_gap"]
    return res
