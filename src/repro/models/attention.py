"""GQA attention: init + train/prefill/decode paths, KV + ring-buffer caches.

The dense softmax path here is the *reference* implementation; on TPU the
Pallas kernels (``repro.kernels.ops.flash_attention`` / ``decode_attention``)
replace the inner computation — see ``repro.kernels.ops.use_pallas``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .common import dense_init, head_rms_norm, rope

_NEG = -1e30


class KVCache(NamedTuple):
    """Global-attention cache: full-length K/V plus the write position."""
    k: jax.Array   # (B, S_max, KV, hd)
    v: jax.Array


class RingCache(NamedTuple):
    """Sliding-window cache: fixed ``window`` slots + absolute positions."""
    k: jax.Array       # (B, W, KV, hd)
    v: jax.Array
    pos: jax.Array     # (B, W) int32 absolute position of each slot, -1 empty


def init_attention(key, cfg, *, cross: bool = False, prefix: str = ""):
    """Parameters for one attention sub-block (self or cross)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv
    ks = jax.random.split(key, 4)
    dt = _pdtype(cfg)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dt),
        "wk": dense_init(ks[1], (d, kv * hd), dt),
        "wv": dense_init(ks[2], (d, kv * hd), dt),
        "wo": dense_init(ks[3], (h * hd, d), dt, scale=1.0 / jnp.sqrt(h * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def _pdtype(cfg):
    from .common import dtype_of
    return dtype_of(cfg.param_dtype)


def _project_q(p, cfg, x):
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(*x.shape[:-1], h, hd)
    if "q_norm" in p:
        q = head_rms_norm(q, p["q_norm"])
    return q


def _project_kv(p, cfg, x):
    kv, hd = cfg.n_kv, cfg.resolved_head_dim
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(*x.shape[:-1], kv, hd)
    v = v.reshape(*x.shape[:-1], kv, hd)
    if "k_norm" in p:
        k = head_rms_norm(k, p["k_norm"])
    return k, v


def self_attention(p, cfg, x, positions, *, kind: str, pad_mask=None):
    """Train/prefill full-sequence self-attention.  kind: g|l|e.

    ``positions`` may be (S,) or per-row (B, S) -- left-padded batches pass
    shifted positions so RoPE sees each row's true token index.  ``pad_mask``
    (B, S) marks valid (non-pad) positions; see ``ops.flash_attention``.
    """
    from ..kernels import ops
    q = _project_q(p, cfg, x)
    k, v = _project_kv(p, cfg, x)
    if cfg.rope_theta and kind != "e_nopos":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    akind = {"l": "local", "e": "full"}.get(kind, "causal")
    out = ops.flash_attention(q, k, v, kind=akind, window=cfg.window,
                              pad_mask=pad_mask)
    out = out.reshape(*x.shape[:-1], -1)
    return out @ p["wo"], (k, v)


def cross_attention(p, cfg, x, context_kv):
    """Cross-attention against precomputed context K/V (no RoPE)."""
    from ..kernels import ops
    q = _project_q(p, cfg, x)
    k, v = context_kv
    out = ops.flash_attention(q, k, v, kind="full")
    out = out.reshape(*x.shape[:-1], -1)
    return out @ p["wo"]


def context_kv(p, cfg, context):
    """Precompute cross-attention K/V from context embeddings (prefill)."""
    return _project_kv(p, cfg, context)


# -- caches -----------------------------------------------------------------

def init_kv_cache(cfg, batch: int, s_max: int, dtype) -> KVCache:
    kv, hd = cfg.n_kv, cfg.resolved_head_dim
    return KVCache(k=jnp.zeros((batch, s_max, kv, hd), dtype),
                   v=jnp.zeros((batch, s_max, kv, hd), dtype))


def init_ring_cache(cfg, batch: int, dtype) -> RingCache:
    kv, hd, w = cfg.n_kv, cfg.resolved_head_dim, cfg.window
    return RingCache(k=jnp.zeros((batch, w, kv, hd), dtype),
                     v=jnp.zeros((batch, w, kv, hd), dtype),
                     pos=jnp.full((batch, w), -1, jnp.int32))


def prefill_into_kv(cache: KVCache, k, v) -> KVCache:
    return KVCache(k=jax.lax.dynamic_update_slice_in_dim(cache.k, k, 0, 1),
                   v=jax.lax.dynamic_update_slice_in_dim(cache.v, v, 0, 1))


def prefill_into_ring(cache: RingCache, k, v, length: int) -> RingCache:
    """Store the last ``window`` entries of a prefilled sequence, placed at
    their ring slots (slot = pos % window) so decode writes continue cleanly."""
    w = cache.k.shape[1]
    s = k.shape[1]
    take = min(w, s)
    pos = jnp.arange(s - take, s)                      # absolute positions
    slots = pos % w
    k_tail = k[:, s - take:]
    v_tail = v[:, s - take:]
    new_k = cache.k.at[:, slots].set(k_tail)
    new_v = cache.v.at[:, slots].set(v_tail)
    new_pos = cache.pos.at[:, slots].set(pos[None, :])
    return RingCache(k=new_k, v=new_v, pos=new_pos)


def decode_self_attention(p, cfg, x, cache, pos, *, kind: str, pad=None):
    """Single-token decode: x (B, 1, D); returns (out, new_cache).

    ``pos`` is the shared cache write position (synchronized batch).  For a
    left-padded batch, ``pad`` (B,) gives each row's pad count: RoPE uses the
    semantic position ``pos - pad`` and cache slots below ``pad`` (the pad
    filler K/V written during prefill) are masked invalid.
    """
    q = _project_q(p, cfg, x)               # (B, 1, H, hd)
    k_new, v_new = _project_kv(p, cfg, x)   # (B, 1, KV, hd)
    if cfg.rope_theta:
        if pad is None:
            pvec = jnp.asarray(pos)[None]           # (1,) shared position
        else:
            pvec = (pos - pad)[:, None]             # (B, 1) per-row position
        q = rope(q, pvec, cfg.rope_theta)
        k_new = rope(k_new, pvec, cfg.rope_theta)

    from ..kernels import ops
    k_new = k_new.astype(cache.k.dtype)
    v_new = v_new.astype(cache.v.dtype)
    if kind == "l":
        w = cache.k.shape[1]
        slot = pos % w
        k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, slot, 1)
        v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, slot, 1)
        pos_buf = jax.lax.dynamic_update_slice_in_dim(
            cache.pos, jnp.full((cache.pos.shape[0], 1), pos, jnp.int32), slot, 1)
        valid = (pos_buf >= 0) & (pos_buf >= pos - w + 1)   # (B, W)
        if pad is not None:
            valid = valid & (pos_buf >= pad[:, None])
        out = ops.decode_attention(q, k, v, valid_mask=valid)
        new_cache = RingCache(k=k, v=v, pos=pos_buf)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, pos, 1)
        v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, pos, 1)
        slots = jnp.arange(k.shape[1])
        valid = (slots <= pos)[None, :]                     # (1, S_max)
        valid = jnp.broadcast_to(valid, (k.shape[0], k.shape[1]))
        if pad is not None:
            valid = valid & (slots[None, :] >= pad[:, None])
        out = ops.decode_attention(q, k, v, valid_mask=valid)
        new_cache = KVCache(k=k, v=v)
    out = out.reshape(*x.shape[:-1], -1)
    return out @ p["wo"], new_cache


def chunk_self_attention(p, cfg, x, cache: KVCache, start, positions):
    """Resumable chunked prefill for global attention: x (B, C, D) holds the
    chunk's C tokens at absolute positions ``positions = start + arange(C)``;
    ``cache`` is a dense (B, S_max, KV, hd) scratch already holding the first
    ``start`` tokens' K/V.  Writes the chunk's K/V at ``start`` and attends
    with the prefix-causal mask (see :func:`ops.chunk_attention`), so the
    result for every valid row matches the whole-prompt prefill exactly.

    ``start`` may be traced: one compiled program serves every chunk index.
    Rows past the prompt's true length (the right-padded final chunk) produce
    junk outputs and junk scratch entries beyond the prompt -- callers slice
    logits at the last valid row and never commit positions >= the prompt
    length (``kvpool.commit_chunk``).
    """
    from ..kernels import ops
    q = _project_q(p, cfg, x)               # (B, C, H, hd)
    k_new, v_new = _project_kv(p, cfg, x)   # (B, C, KV, hd)
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k_new = rope(k_new, positions, cfg.rope_theta)
    k = jax.lax.dynamic_update_slice_in_dim(
        cache.k, k_new.astype(cache.k.dtype), start, 1)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache.v, v_new.astype(cache.v.dtype), start, 1)
    out = ops.chunk_attention(q, k, v, start=start)
    out = out.reshape(*x.shape[:-1], -1)
    return out @ p["wo"], KVCache(k=k, v=v)


def paged_write_target(block_table, seq_lens, block_size: int):
    """Where each row's new K/V lands in the pool: block
    ``block_table[i, seq_lens[i] // block_size]`` at offset
    ``seq_lens[i] % block_size``.  Returns ``(blk, off)``, both (B,)."""
    rows = jnp.arange(block_table.shape[0])
    return block_table[rows, seq_lens // block_size], seq_lens % block_size


def write_paged(pool: KVCache, new: KVCache, blk, off) -> KVCache:
    """Scatter new K/V rows into a pool at ``(blk, off)`` (see
    :func:`paged_write_target`): ``pool`` is ``(n_blocks, bs, KV, hd)`` with
    ``new`` ``(B, KV, hd)``, or stacked ``(U, n_blocks, bs, KV, hd)`` with
    ``new`` ``(U, B, KV, hd)`` -- every unit's rows in one scatter.  On a
    donated pool the scatter writes in place."""
    return KVCache(k=pool.k.at[..., blk, off, :, :].set(new.k),
                   v=pool.v.at[..., blk, off, :, :].set(new.v))


def decode_self_attention_paged(p, cfg, x, cache, *, kind: str,
                                block_table, seq_lens, layer=None):
    """Single-token decode against per-slot caches (continuous batching).

    Unlike :func:`decode_self_attention` there is no shared write frontier
    and no pad vector: each row ``i`` carries its own cache length
    ``seq_lens[i]`` (the position being written) and the caches are
    pad-free (see ``serving.kvpool.commit_prefill``).

    * ``kind == "g"``: ``cache`` is a pool :class:`KVCache`, READ-ONLY here:
      ``(n_blocks, block_size, KV, hd)``, or the stacked
      ``(U, n_blocks, block_size, KV, hd)`` of a layer scan with ``layer``
      the scan's unit index.  ``block_table`` (B, M) maps each row's logical
      block index to a pool block.  Each row gathers its blocks into a
      contiguous ``(B, M*bs)`` view straight from the pool, puts its new K/V
      into that view at ``seq_lens[i]``, and attends with positions
      ``> seq_lens[i]`` masked.  Returns the new rows as a ``(B, KV, hd)``
      :class:`KVCache` in place of a pool: the caller writes them once
      with :func:`write_paged` at :func:`paged_write_target` (block
      ``block_table[i, seq_lens[i] // bs]``, offset ``seq_lens[i] % bs``),
      so a donated pool is updated in place and never copied.  Idle rows
      (``seq_lens == 0``, table all zeros) write to the reserved dummy
      block 0 -- harmless garbage nobody gathers as valid beyond position
      0, and their outputs are discarded by the engine.
    * ``kind == "l"``: ``cache`` is a per-slot :class:`RingCache`; row
      ``i`` writes its ring slot ``seq_lens[i] % window`` (semantic
      positions -- commit re-slots prefill entries) and the new ring is
      returned.
    """
    from ..kernels import ops
    q = _project_q(p, cfg, x)               # (B, 1, H, hd)
    k_new, v_new = _project_kv(p, cfg, x)   # (B, 1, KV, hd)
    if cfg.rope_theta:
        pvec = seq_lens[:, None]                    # (B, 1) per-row position
        q = rope(q, pvec, cfg.rope_theta)
        k_new = rope(k_new, pvec, cfg.rope_theta)
    k_new = k_new.astype(cache.k.dtype)[:, 0]
    v_new = v_new.astype(cache.v.dtype)[:, 0]
    b = x.shape[0]
    rows = jnp.arange(b)

    if kind == "l":
        w = cache.k.shape[1]
        slot = seq_lens % w                                  # (B,)
        k = cache.k.at[rows, slot].set(k_new)
        v = cache.v.at[rows, slot].set(v_new)
        pos_buf = cache.pos.at[rows, slot].set(seq_lens)
        valid = (pos_buf >= 0) & (pos_buf >= (seq_lens - w + 1)[:, None])
        out = ops.decode_attention(q, k, v, valid_mask=valid)
        new_cache = RingCache(k=k, v=v, pos=pos_buf)
    else:
        bs, kvh, hd = cache.k.shape[-3:]
        m = block_table.shape[1]
        idx = block_table if layer is None else (layer, block_table)
        k_rows = cache.k[idx].reshape(b, m * bs, kvh, hd)
        v_rows = cache.v[idx].reshape(b, m * bs, kvh, hd)
        k_rows = k_rows.at[rows, seq_lens].set(k_new)
        v_rows = v_rows.at[rows, seq_lens].set(v_new)
        valid = jnp.arange(m * bs)[None, :] <= seq_lens[:, None]
        out = ops.decode_attention(q, k_rows, v_rows, valid_mask=valid)
        new_cache = KVCache(k=k_new, v=v_new)
    out = out.reshape(*x.shape[:-1], -1)
    return out @ p["wo"], new_cache


def decode_cross_attention(p, cfg, x, context_cache):
    q = _project_q(p, cfg, x)
    k, v = context_cache
    from ..kernels import ops
    valid = jnp.ones((k.shape[0], k.shape[1]), bool)
    out = ops.decode_attention(q, k, v, valid_mask=valid)
    out = out.reshape(*x.shape[:-1], -1)
    return out @ p["wo"]
