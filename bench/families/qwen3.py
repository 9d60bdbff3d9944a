"""The Qwen3 family (``model_type`` "qwen3"): a configuration file in, the
engine's model, the run's weights, the reference's logits and the counts
of operations and bytes out.

The weights are the benchmark's own, made by ``bench/weights.py`` in a
plain layout that ``reference/qwen3.py`` reads; ``engine_params`` only
renames that layout into the engine's tree and copies no array.

The counts are of the work the model needs, whatever implements it: no
padding, no bucket slack, no rows of idle slots, and a causal prompt
attends only to earlier positions.
"""
from __future__ import annotations

from bench import weights
from bench.reference import qwen3 as reference

BF16 = 2
# standard deviation of the norm offsets, so that the norm scales take
# part; every matrix takes the configuration's initializer_range
NORM_STD = 0.1


def arch_config(cfg: dict):
    """The engine's ``ArchConfig`` for a Qwen3 configuration file."""
    from repro.configs.base import ArchConfig
    if cfg.get("attention_bias"):
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
    std = cfg["initializer_range"]
    return weights.init(weight_shapes(cfg), key, cfg["dtype"],
                        lambda name: NORM_STD if "norm" in name else std)


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


# the plain float32 reference; precision="fp8" is the control
logits = reference.logits


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["num_hidden_layers"], cfg["vocab_size"])


def layer_matmul_params(cfg: dict) -> int:
    d, f, hd, h, kv, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def param_count(cfg: dict) -> int:
    """Every parameter: embedding (tied head), layers with their norms,
    final norm."""
    d, _, hd, _, _, n, v = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * d + 2 * hd
    head = 0 if cfg["tie_word_embeddings"] else v * d
    return v * d + head + n * per_layer + d


def head_flops(cfg: dict) -> int:
    d, *_, v = _dims(cfg)
    return 2 * d * v


def decode_token_flops(cfg: dict, ctx: int) -> int:
    """One decoded token whose attention reads ``ctx`` positions (itself
    included), with its logits."""
    _, _, hd, h, _, n, _ = _dims(cfg)
    return (n * (2 * layer_matmul_params(cfg) + 4 * h * hd * ctx)
            + head_flops(cfg))


def prefill_flops(cfg: dict, prompt: int) -> int:
    """A whole prompt, causal, with the logits of its last position."""
    _, _, hd, h, _, n, _ = _dims(cfg)
    attn = 2 * h * hd * prompt * (prompt + 1)     # sum over i of 4 h hd i
    return (n * (2 * layer_matmul_params(cfg) * prompt + attn)
            + head_flops(cfg))


def decode_attn_flops(cfg: dict, ctx: int) -> int:
    """Decode attention of one token over ``ctx`` positions, all layers."""
    _, _, hd, h, _, n, _ = _dims(cfg)
    return n * 4 * h * hd * ctx


def decode_attn_bytes(cfg: dict, ctx: int) -> int:
    """Bytes decode attention needs for one token, all layers: its query,
    the ``ctx`` live key and value rows, its output."""
    _, _, hd, h, kv, n, _ = _dims(cfg)
    return n * BF16 * (2 * h * hd + 2 * ctx * kv * hd)


def kv_bytes_per_token(cfg: dict) -> int:
    _, _, hd, _, kv, n, _ = _dims(cfg)
    return n * 2 * kv * hd * BF16
