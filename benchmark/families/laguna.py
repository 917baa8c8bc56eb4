"""Family ``laguna``: Laguna-S-2.1 (``LagunaConfig`` / ``PipelinedLaguna``),
a chip's share of it. Glue between the benchmark's own weights, arithmetic
and plain reference, and the program's objects."""

from __future__ import annotations

import jax.numpy as jnp

from pb_core import load_by_path

reference = load_by_path("reference/laguna.py")

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
_ATTENTION = {"full_attention": "full", "sliding_attention": "sliding"}


def model_config(cfg: dict):
    from pipe_tpu.models.laguna import LagunaConfig
    kinds = [_ATTENTION[t] for t in cfg["layer_types"]]
    period = kinds[:4]
    if kinds != (period * (len(kinds) // 4 + 1))[:len(kinds)]:
        raise ValueError("layer_types is not one period of four repeated")
    heads = dict(zip(kinds, cfg["num_attention_heads_per_layer"]))
    rp = cfg["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default":
        raise ValueError("rope_parameters: yarn on full layers and default "
                         "on sliding layers is what the program builds")
    return LagunaConfig(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        n_layers=cfg["n_layers"], period=tuple(period),
        heads_full=heads["full"], heads_sliding=heads["sliding"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], d_ff=cfg["intermediate_size"],
        mlp_only_layers=tuple(cfg["mlp_only_layers"]),
        num_experts=cfg["published"]["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=tuple(cfg["experts_held"]),
        moe_d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["shared_expert_intermediate_size"],
        routed_scale=cfg["moe_routed_scaling_factor"],
        rope_full={"theta": float(full["rope_theta"]),
                   "fraction": full["partial_rotary_factor"],
                   "yarn": {"factor": full["factor"],
                            "original":
                                full["original_max_position_embeddings"],
                            "beta_fast": full["beta_fast"],
                            "beta_slow": full["beta_slow"],
                            "attention_factor": full.get("attention_factor")}},
        rope_sliding={"theta": float(sliding["rope_theta"]),
                      "fraction": sliding["partial_rotary_factor"]},
        rms_eps=cfg["rms_norm_eps"],
        max_positions=cfg["max_position_embeddings"],
        compute_dtype=_DTYPES[cfg["compute_dtype"]])


def build_model(cfg: dict, n_stages: int):
    from pipe_tpu.models.laguna import PipelinedLaguna
    return PipelinedLaguna(model_config(cfg), n_stages)


def serve_params(weights):
    """The program's ``(stage_params, pre_params, post_params)`` over the
    benchmark's arrays themselves: one stage, a stacked tree a group of like
    layers, as the reference makes them. Nothing is copied or restacked."""
    stacks = []
    for g in weights["groups"]:
        p = {"attn": {k: g[k] for k in ("wq", "wk", "wv", "wo", "wg")},
             "ln1": {"g": g["ln1_g"]}, "ln2": {"g": g["ln2_g"]}}
        if "router" in g:
            p["moe"] = {"router": g["router"], "w_gate": g["e_gate"],
                        "w_up": g["e_up"], "w_down": g["e_down"]}
            p["shared"] = {"w_gate": g["s_gate"], "w_up": g["s_up"],
                           "w_down": g["s_down"]}
        else:
            p["mlp"] = {k: g[k] for k in ("w_gate", "w_up", "w_down")}
        stacks.append(p)
    pre = {"embed": {"table": weights["embed"]}}
    post = {"head": {"ln_f": {"g": weights["lnf_g"]},
                     "proj": {"w": weights["head_w"]}}}
    return [stacks], pre, post


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, nothing the engine adds


def _layers(cfg: dict):
    """``(query heads, is an expert layer, has a window)`` of each held
    layer."""
    return [(heads, ffn == "moe", attention == "sliding_attention")
            for attention, ffn, heads in reference.layer_kinds(cfg)]


def _attention_params(cfg: dict, heads: int) -> int:
    d, hd, hkv = (cfg["hidden_size"], cfg["head_dim"],
                  cfg["num_key_value_heads"])
    return 2 * d * heads * hd + 2 * d * hkv * hd + d * heads


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices of hidden x width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def non_expert_params(cfg: dict) -> int:
    """The layers' matrices that every token is multiplied through whatever
    it is routed to: attention and its gate, the dense layer's MLP, the
    router, the shared expert. Without the head."""
    d, total = cfg["hidden_size"], 0
    for heads, moe, _ in _layers(cfg):
        total += _attention_params(cfg, heads)
        if moe:
            total += (d * cfg["published"]["num_experts"]
                      + 3 * d * cfg["shared_expert_intermediate_size"])
        else:
            total += 3 * d * cfg["intermediate_size"]
    return total


def expected_held_picks(cfg: dict) -> float:
    """Of a token's picks, how many land on an expert held here when the
    router is uniform: 10 x 128 / 256 = 5."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def forward_flops_per_token(cfg: dict) -> float:
    """2 x (the layers' non-expert matrices + the routed experts at the
    expectation of held picks, in every expert layer). Left out, so a share
    of the peak built on this errs under and never over: attention's own
    score and value products (4 x context x heads x 128 a layer), and the
    head (2 x hidden x vocabulary held, a quarter again), which an output
    token goes through and a prompt's tokens, but for the last, do not."""
    n_moe = sum(1 for _, moe, _ in _layers(cfg) if moe)
    return 2.0 * (non_expert_params(cfg)
                  + n_moe * expected_held_picks(cfg) * expert_params(cfg))


def decode_weight_bytes(cfg: dict) -> float:
    """A LOWER bound on the weight bytes one decode step reads: the
    non-expert matrices and the head at their served type. The routed
    experts a step touches are counted from the program's own counter by
    ``decode.routed_step_roofline``, not here."""
    return float((non_expert_params(cfg)
                  + cfg["hidden_size"] * cfg["vocab"])
                 * jnp.dtype(cfg["compute_dtype"]).itemsize)


def kv_row_bytes(cfg: dict) -> float:
    """A LOWER bound on the bytes of one cached position of one sequence:
    the full-attention layers' keys and values only (a window layer's rows
    stop growing at ``sliding_window``)."""
    width = jnp.dtype(cfg["compute_dtype"]).itemsize
    full = sum(1 for _, _, window in _layers(cfg) if not window)
    return float(full * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
                 * width)


def cache_row_bytes(cfg: dict) -> float:
    """Bytes of one cached position of one sequence in ONE layer, of either
    kind: a key and a value of 8 heads of 128 (4,096 B in bfloat16)."""
    return float(2 * cfg["num_key_value_heads"] * cfg["head_dim"]
                 * jnp.dtype(cfg["compute_dtype"]).itemsize)


def expert_bytes(cfg: dict) -> float:
    """Bytes of one routed expert's weights at their served type."""
    return float(expert_params(cfg)
                 * jnp.dtype(cfg["compute_dtype"]).itemsize)


def expert_flops_per_row(cfg: dict) -> float:
    """FLOPs of one token-expert pair: 2 x 3 x hidden x width."""
    return 2.0 * expert_params(cfg)


num_params = reference.num_params
