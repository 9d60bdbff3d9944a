"""Operations and bytes of a Qwen3 configuration, from its sizes and the
live lengths.  They count the work the model needs, whatever implements
it: no padding, no bucket slack, no rows of idle slots, and a causal
prompt attends only to earlier positions."""
from __future__ import annotations

BF16 = 2


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
