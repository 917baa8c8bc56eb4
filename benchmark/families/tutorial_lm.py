"""Family ``tutorial_lm``: the tutorial language model (``LMConfig`` /
``PipelinedLM``). Glue between the benchmark's own weights, arithmetic and
plain reference, and the program's objects."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pb_core import load_by_path

reference = load_by_path("reference/tutorial_lm.py")

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# program block-leaf path -> the reference's stacked layer kind
_BLOCK_LEAVES = {
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo", ("attn", "bq"): "bq", ("attn", "bk"): "bk",
    ("attn", "bv"): "bv", ("attn", "bo"): "bo",
    ("ff1", "w"): "ff1_w", ("ff1", "b"): "ff1_b",
    ("ff2", "w"): "ff2_w", ("ff2", "b"): "ff2_b",
    ("ln1", "g"): "ln1_g", ("ln1", "b"): "ln1_b",
    ("ln2", "g"): "ln2_g", ("ln2", "b"): "ln2_b",
}


def model_config(cfg: dict):
    from pipe_tpu.models.transformer_lm import LMConfig
    return LMConfig(vocab=cfg["vocab"], d_model=cfg["d_model"],
                    nhead=cfg["nhead"], d_ff=cfg["d_ff"],
                    n_layers=cfg["n_layers"], dropout=cfg["dropout"],
                    seq_len=cfg["seq_len"], causal=cfg["causal"],
                    compute_dtype=_DTYPES[cfg["compute_dtype"]])


def build_model(cfg: dict, n_stages: int):
    from pipe_tpu.models.transformer_lm import PipelinedLM
    return PipelinedLM(model_config(cfg), n_stages)


def _layout_norms(a, b=None) -> dict:
    """The reference's ``leaf_norms`` of ``a`` (of ``a - b`` where ``b`` is
    given), both in the trainer's layout, in one fused program: the
    difference is never held."""
    @jax.jit
    def norms(a, b):
        def n(path, axes=None):
            x = path(a).astype(jnp.float32)
            if b is not None:
                x = x - path(b).astype(jnp.float32)
            return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
        out = {"embed": n(lambda t: t[1]["embed"]["table"]).reshape(1),
               "dec_w": n(lambda t: t[2]["decoder"]["w"]).reshape(1),
               "dec_b": n(lambda t: t[2]["decoder"]["b"]).reshape(1)}
        for (mod, leaf), kind in _BLOCK_LEAVES.items():
            # [layers per stage, stages] -> layer s * lps + l
            per = jnp.stack([
                n(lambda t, l=l: t[0][l][mod][leaf],
                  tuple(range(1, a[0][l][mod][leaf].ndim)))
                for l in range(len(a[0]))])
            out[kind] = per.T.reshape(-1)
        return out

    return {k: np.asarray(v, np.float64) for k, v in norms(a, b).items()}


def train_leaf_norms(tree, scale: float = 1.0) -> dict:
    """The reference's ``leaf_norms`` of a tree in the trainer's layout
    (parameters, or one of Adam's moments), times ``scale``."""
    return {k: v * scale for k, v in _layout_norms(tree).items()}


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, nothing the executor adds


def train_flops_per_token(cfg: dict) -> float:
    """Required FLOPs per trained token: forward matrix products (QKV and
    output projections 4 d^2, feed-forward 2 d d_ff, causal attention scores
    and values 2 (s/2) d per layer, decoder d V), one multiply-add = 2 FLOPs,
    backward = 2 x forward. Recomputation is not counted."""
    d, ff, L, V = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    eff_s = cfg["seq_len"] / 2 if cfg["causal"] else cfg["seq_len"]
    macs = L * (4 * d * d + 2 * d * ff + 2 * eff_s * d) + d * V
    return 2.0 * macs * 3


def train_bytes_per_step(cfg: dict, rows: int) -> float:
    """Bytes a step must move through HBM at the least: float32 weights read
    once for the forward and once for the backward, gradients written and
    read, Adam's two moments read and written, weights written (7 passes of
    4 bytes a parameter), plus each layer's bfloat16 boundary activation
    written and read once."""
    n = reference.num_params(cfg)
    act = rows * cfg["seq_len"] * cfg["d_model"] * 2 * 2 * cfg["n_layers"]
    return 7.0 * 4 * n + act


num_params = reference.num_params


def make_train_params(cfg: dict, seed: int, n_stages: int):
    """The benchmark's weights for ``seed`` made straight into the trainer's
    layout ``(stage-stacked blocks, pre, post)``, one jitted call, leaf by
    leaf from the reference's own keys: block ``l`` of the list holds, on a
    leading stage axis, layer ``s * layers_per_stage + l`` of every stage
    ``s``. The same values as ``reference.make_weights`` gives, without
    both layouts on the device at once."""
    d, ff, L, V = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    lps = L // n_stages

    @jax.jit
    def make(key):
        blocks = []
        for l in range(lps):
            bp = {}
            for (mod, leaf), kind in _BLOCK_LEAVES.items():
                bp.setdefault(mod, {})[leaf] = jnp.stack([
                    reference.layer_leaf(key, kind, s * lps + l, d, ff)
                    for s in range(n_stages)])
            blocks.append(bp)
        embed, dec_w, dec_b = reference.outer_leaves(key, V, d)
        return (blocks, {"embed": {"table": embed}},
                {"decoder": {"w": dec_w, "b": dec_b}})

    return make(reference.seed_key(seed))


def train_change_norms(a, b) -> dict:
    """The reference's ``leaf_norms`` of ``a - b`` (both in the trainer's
    layout)."""
    return _layout_norms(a, b)


def train_grad_sample(tree, n_layers: int, scale: float = 1.0) -> dict:
    """The reference's ``grad_sample`` of a tree in the trainer's layout,
    times ``scale``, copied to the host (so that nothing of it stays on the
    device through the window)."""
    blocks, pre, post = tree
    lps = len(blocks)
    out = {"embed": pre["embed"]["table"], "dec_w": post["decoder"]["w"],
           "dec_b": post["decoder"]["b"]}
    for l in reference.sample_layers(n_layers):
        for (mod, leaf), kind in _BLOCK_LEAVES.items():
            out[f"{kind}[{l}]"] = blocks[l % lps][mod][leaf][l // lps]
    return {k: np.asarray(v, np.float32) * np.float32(scale)
            for k, v in out.items()}
