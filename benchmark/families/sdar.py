"""Family ``sdar``: SDAR-30B-A3B-Chat (``SdarConfig`` / ``PipelinedSdar``),
the first stage of a pipeline that serves it. Glue between the benchmark's
own weights, arithmetic and plain reference, and the program's objects."""

from __future__ import annotations

import jax.numpy as jnp

from pb_core import load_by_path

reference = load_by_path("reference/sdar.py")

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(cfg: dict):
    from pipe_tpu.models.sdar import SdarConfig
    if cfg["num_experts"] != cfg["published"]["num_experts"] \
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("every layer holds every routed expert: that is "
                         "what the program builds")
    g = cfg["generation"]
    if g["remasking"] != "low_confidence_static" or g["shift"]:
        raise ValueError("generation: low_confidence_static without a "
                         "shift is what the program builds")
    return SdarConfig(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        n_layers=cfg["n_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        max_positions=cfg["max_position_embeddings"],
        block_length=g["block_length"], denoise_steps=g["denoise_steps"],
        mask_token_id=g["mask_token_id"],
        compute_dtype=_DTYPES[cfg["compute_dtype"]])


def build_model(cfg: dict, n_stages: int):
    from pipe_tpu.models.sdar import PipelinedSdar
    return PipelinedSdar(model_config(cfg), n_stages)


def serve_params(weights):
    """The program's ``(stage_params, pre_params, post_params)`` over the
    benchmark's arrays themselves: one stage, one group, its leaves stacked
    on the layers axis as the reference makes them. Nothing is copied."""
    g = weights["layers"]
    stack = {"attn": {k: g[k] for k in ("wq", "wk", "wv", "wo", "gq", "gk")},
             "ln1": {"g": g["ln1_g"]}, "ln2": {"g": g["ln2_g"]},
             "moe": {"router": g["router"], "w_gate": g["e_gate"],
                     "w_up": g["e_up"], "w_down": g["e_down"]}}
    pre = {"embed": {"table": weights["embed"]}}
    post = {"head": {"ln_f": {"g": weights["lnf_g"]},
                     "proj": {"w": weights["head_w"]}}}
    return [[stack]], pre, post


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, nothing the engine adds


def _width(cfg: dict) -> int:
    return jnp.dtype(cfg["compute_dtype"]).itemsize


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices of hidden x width (4,718,592)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_non_expert_params(cfg: dict) -> int:
    """A layer outside its experts: wq, wo, wk, wv, the router, the two
    norms' gains and the two QK-norm gains (19,140,864)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2 * d * h * hd + 2 * d * hkv * hd + d * cfg["num_experts"]
            + 2 * d + 2 * hd)


def non_expert_params(cfg: dict) -> int:
    """The held layers' parameters that every row is multiplied through
    whatever it is routed to. Without the embedding and the head."""
    return cfg["n_layers"] * layer_non_expert_params(cfg)


def forward_flops_per_token(cfg: dict) -> float:
    """2 x (the layers' non-expert matrices + a token's eight experts in
    every layer). Left out, so a share of the peak built on this errs
    under and never over: attention's own score and value products, the
    head (a denoise pass's rows go through it, a commit pass's and a
    prompt's do not), and that a generated token is computed in more than
    one pass."""
    return 2.0 * (non_expert_params(cfg) + cfg["n_layers"]
                  * cfg["num_experts_per_tok"] * expert_params(cfg))


def decode_weight_bytes(cfg: dict) -> float:
    """A LOWER bound on the weight bytes EVERY pass of a block reads, a
    denoise pass or the commit pass: the layers' non-expert matrices at
    their served type. The head (`head_bytes`) is a denoise pass's alone,
    and the routed experts a pass touches are counted from the program's
    own counter."""
    return float(non_expert_params(cfg) * _width(cfg))


def head_bytes(cfg: dict) -> float:
    """The head's matrix at its served type: what a denoise pass reads
    beside `decode_weight_bytes`, and the commit pass does not."""
    return float(cfg["hidden_size"] * cfg["vocab"] * _width(cfg))


def cache_row_bytes(cfg: dict) -> float:
    """Bytes of one cached position of one sequence in ONE layer: a key
    and a value of 4 heads of 128 (2,048 B in bfloat16)."""
    return float(2 * cfg["num_key_value_heads"] * cfg["head_dim"]
                 * _width(cfg))


def kv_row_bytes(cfg: dict) -> float:
    """Bytes of one cached position of one sequence in all held layers."""
    return cfg["n_layers"] * cache_row_bytes(cfg)


def expert_bytes(cfg: dict) -> float:
    """Bytes of one routed expert's weights at their served type."""
    return float(expert_params(cfg) * _width(cfg))


def expert_flops_per_row(cfg: dict) -> float:
    """FLOPs of one token-expert pair: 2 x 3 x hidden x width."""
    return 2.0 * expert_params(cfg)


num_params = reference.num_params
