"""Plain float32 Qwen3 forward pass: the oracle for ``correct``.

Written from the published architecture (Qwen3ForCausalLM): token
embedding; per layer RMSNorm -> q/k/v projections -> per-head RMSNorm of
q and k -> rotary embedding (theta from the config, halves rotated) ->
grouped-query causal softmax attention (query head h reads kv head
h // (heads / kv heads), scale head_dim^-0.5) -> output projection ->
residual; RMSNorm -> SwiGLU (down(silu(gate x) * up x)) -> residual; final
RMSNorm; the head tied to the embedding.  It imports nothing of the
program.  The one departure is the parameterization of the norm scales,
which the weight layout stores as offsets w of the scale 1 + w.

Every matrix product runs at ``precision="highest"``: a float32 product
on the TPU is otherwise computed from bfloat16 passes.  The weights stay
in their served type on the device and one layer at a time is widened
(a scan over the stacked layers), so a 4B model fits beside its own
weights.

``precision="fp8"`` is the control: the same pass with every projection
computed from float8 e4m3 operands, weights scaled per output channel and
activations per token -- the step below the bfloat16 that the
configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _fp8(x, axis):
    """Round trip through float8 e4m3 with one scale per slice along
    ``axis``, the contracted one (its largest value maps to 448)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(F32))


def _rope(x, theta):
    """x (B, S, H, hd) at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, fp8, x, lw):
    b, s, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    lw = jax.tree.map(lambda a: a.astype(F32), lw)
    a = _rms(x, lw["attn_norm"], eps)
    q = _mm(a, lw["wq"], fp8).reshape(b, s, h, hd)
    k = _mm(a, lw["wk"], fp8).reshape(b, s, kv, hd)
    v = _mm(a, lw["wv"], fp8).reshape(b, s, kv, hd)
    q = _rope(_rms(q, lw["q_norm"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, lw["k_norm"], eps), cfg["rope_theta"])
    q = q.reshape(b, s, kv, h // kv, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        precision="highest") * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision="highest")
    x = x + _mm(o.reshape(b, s, h * hd), lw["wo"], fp8)
    m = _rms(x, lw["mlp_norm"], eps)
    g = _mm(m, lw["w_gate"], fp8)
    u = _mm(m, lw["w_up"], fp8)
    return x + _mm(jax.nn.silu(g) * u, lw["w_down"], fp8), None


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _logits(w, tokens, rows, cfg_items, fp8):
    cfg = dict(cfg_items)
    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(functools.partial(_layer, cfg, fp8), x, w["layers"])
    x = _rms(x, w["final_norm"], cfg["rms_norm_eps"])
    sel = jnp.take_along_axis(x, rows[..., None], axis=1)   # (B, P, d)
    return _mm(sel, w["embed"].astype(F32).T, fp8)


def logits(w, cfg: dict, tokens, rows, *, precision: str = "float32"):
    """Logits (B, P, vocab) in float32 of the positions ``rows`` (B, P) of
    the token rows ``tokens`` (B, S)."""
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown reference precision {precision!r}")
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    items = tuple((k, cfg[k]) for k in keys)
    return _logits(w, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(rows, jnp.int32), items, precision == "fp8")
