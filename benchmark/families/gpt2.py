"""Family ``gpt2``: GPT-2 (``GPT2Config`` / ``PipelinedGPT2``). Glue between
the benchmark's own weights, arithmetic and plain reference, and the
program's objects."""

from __future__ import annotations

import jax.numpy as jnp

from pb_core import load_by_path

reference = load_by_path("reference/gpt2.py")

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(cfg: dict):
    from pipe_tpu.models.gpt2 import GPT2Config
    return GPT2Config(vocab=cfg["vocab"], d_model=cfg["d_model"],
                      nhead=cfg["nhead"], d_ff=cfg["d_ff"],
                      n_layers=cfg["n_layers"], dropout=cfg["dropout"],
                      seq_len=cfg["seq_len"],
                      compute_dtype=_DTYPES[cfg["compute_dtype"]])


def build_model(cfg: dict, n_stages: int):
    from pipe_tpu.models.gpt2 import PipelinedGPT2
    return PipelinedGPT2(model_config(cfg), n_stages)


def serve_params(weights):
    """The program's ``(stage_params, pre_params, post_params)`` over the
    benchmark's arrays themselves (one stage; nothing is copied)."""
    blocks = [{
        "attn": {k: p[k] for k in ("wq", "wk", "wv", "wo",
                                   "bq", "bk", "bv", "bo")},
        "ff1": {"w": p["ff1_w"], "b": p["ff1_b"]},
        "ff2": {"w": p["ff2_w"], "b": p["ff2_b"]},
        "ln1": {"g": p["ln1_g"], "b": p["ln1_b"]},
        "ln2": {"g": p["ln2_g"], "b": p["ln2_b"]},
    } for p in weights["layers"]]
    pre = {"embed": {"wte": weights["wte"], "wpe": weights["wpe"]}}
    post = {"head": {"ln_f": {"g": weights["lnf_g"], "b": weights["lnf_b"]},
                     "proj": {"w": weights["head_w"]}}}
    return [blocks], pre, post


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, nothing the engine adds


def matmul_params(cfg: dict) -> int:
    """Parameters that a token is multiplied through: the blocks' projection
    and feed-forward matrices and the head (embedding look-ups are not
    FLOPs)."""
    d, ff, L, V = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    return L * (4 * d * d + 2 * d * ff) + d * V


def forward_flops_per_token(cfg: dict) -> float:
    """2 x the matrix parameters. Attention's own score and value products
    (4 x context x d_model a layer) are left out, so a share of the peak
    built on this is a little under the truth and never over it."""
    return 2.0 * matmul_params(cfg)


def decode_weight_bytes(cfg: dict) -> float:
    """Bytes of weights one decode step reads: the blocks at their served
    type, the final norm and the head in float32."""
    d, ff, L, V = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    width = jnp.dtype(cfg["compute_dtype"]).itemsize
    per_layer = 4 * d * d + 4 * d + 2 * d * ff + ff + d + 4 * d
    return float(L * per_layer * width + (d * V + 2 * d) * 4)


def kv_row_bytes(cfg: dict) -> float:
    """Bytes of one cached position of one sequence: a key and a value of
    ``d_model`` in every layer, in the compute type."""
    width = jnp.dtype(cfg["compute_dtype"]).itemsize
    return float(cfg["n_layers"] * 2 * cfg["d_model"] * width)


num_params = reference.num_params
