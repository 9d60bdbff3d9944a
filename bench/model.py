"""A configuration file in, the engine's model and the run's weights out.

The weights are the benchmark's own: ``init_weights`` makes them on the
device, in one jitted call from the seed, in the type they are served in,
and in a plain layout that ``reference/qwen3.py`` reads.  ``engine_params``
only renames that layout into the engine's tree; it copies no array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# standard deviations of the random weights: HF's initializer_range for
# every matrix, and a spread on the norm scales so that they take part
NORM_STD = 0.1


def arch_config(cfg: dict):
    """The engine's ``ArchConfig`` for a Qwen3 configuration file."""
    from repro.configs.base import ArchConfig
    if cfg["model_type"] != "qwen3" or cfg.get("attention_bias"):
        raise ValueError(f"{cfg['name']}: not a Qwen3 configuration")
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), qk_norm=True,
        block_pattern=("g",), tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["dtype"], compute_dtype=cfg["dtype"],
        source=cfg["source"])


def weight_shapes(cfg: dict) -> dict:
    """Shapes of the plain layout: per-layer leaves stacked on axis 0.
    Norm leaves hold the offset w of the scale 1 + w."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    return {
        "embed": (v, d),
        "final_norm": (d,),
        "layers": {
            "attn_norm": (n, d), "q_norm": (n, hd), "k_norm": (n, hd),
            "wq": (n, d, h * hd), "wk": (n, d, kv * hd),
            "wv": (n, d, kv * hd), "wo": (n, h * hd, d),
            "mlp_norm": (n, d), "w_gate": (n, d, f), "w_up": (n, d, f),
            "w_down": (n, f, d),
        },
    }


def init_weights(cfg: dict, key):
    """Random weights on the default device, one jitted call."""
    dtype = jnp.dtype(cfg["dtype"])
    std = cfg["initializer_range"]
    paths, tree = jax.tree.flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in paths]
    leaves = [shape for _, shape in paths]

    def make(key):
        out = []
        for k, shape, name in zip(jax.random.split(key, len(leaves)),
                                  leaves, names):
            scale = NORM_STD if "norm" in name else std
            if len(shape) == 3:     # one layer at a time bounds the temporaries
                one = lambda kk, s=shape[1:], sc=scale: (
                    jax.random.normal(kk, s, jnp.float32) * sc).astype(dtype)
                out.append(jax.lax.map(one, jax.random.split(k, shape[0])))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * scale).astype(dtype))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(key)


def engine_params(w: dict) -> dict:
    """The plain layout renamed into the engine's tree (``block_pattern``
    ("g",): one stacked unit ``slot0``; tied head)."""
    L = w["layers"]
    return {
        "embed": w["embed"],
        "final_norm": w["final_norm"],
        "units": {"slot0": {
            "norm1": L["attn_norm"],
            "attn": {"wq": L["wq"], "wk": L["wk"], "wv": L["wv"],
                     "wo": L["wo"], "q_norm": L["q_norm"],
                     "k_norm": L["k_norm"]},
            "norm2": L["mlp_norm"],
            "ffn": {"w1": L["w_gate"], "w3": L["w_up"], "w2": L["w_down"]},
        }},
    }
