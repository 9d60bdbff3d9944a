"""Pallas TPU decode attention: one query token vs a long (ring/linear) KV
cache, blocked over the cache length.

Grid (B, KV, nk): the single query row per (batch, kv-head) is tiny, so the
kernel is purely memory-bound -- each program streams one (Kb, hd) key tile
and one value tile through VMEM and maintains online-softmax state in
scratch.  ``valid_mask`` (B, S) carries both the causal frontier and ring-
buffer validity (models/attention.py), so one kernel serves linear caches,
sliding-window rings, and cross-attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref, m_ref, l_ref, *,
            g: int, nk: int, scale: float):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)           # (Kb, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    valid = mask_ref[0]                           # (1, Kb)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, Kb)
    s = jnp.where(valid, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[...] = (acc_ref[...] / l)[None, None].astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, *, valid_mask, k_block: int = 512,
                            interpret: bool = False):
    """q (B,1,H,hd), k/v (B,S,KV,hd), valid_mask (B,S) -> (B,1,H,hd).

    Any cache length S is accepted: a ragged tail (S not a k_block
    multiple) is padded wrapper-side up to the next block boundary with
    ``valid_mask=False`` entries, which the in-kernel mask turns into
    ``exp(-inf) == 0`` softmax terms -- same discipline the flash/scan
    kernels use for pad columns, so paged caches with per-slot lengths
    (serving/kvpool.py) need no host-side repacking.
    """
    b, _, h, hd = q.shape
    _, s, kv, _ = k.shape
    g = h // kv
    k_block = min(k_block, s)
    if s % k_block:
        tail = k_block - s % k_block
        wid = [(0, 0), (0, tail), (0, 0), (0, 0)]
        k = jnp.pad(k, wid)
        v = jnp.pad(v, wid)
        valid_mask = jnp.pad(valid_mask, [(0, 0), (0, tail)])   # False tail
        s += tail
    nk = s // k_block

    qr = q.reshape(b, kv, g, hd)
    kr = k.transpose(0, 2, 1, 3)     # (B,KV,S,hd)
    vr = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, g=g, nk=nk, scale=1.0 / (hd ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b_, k_, ik: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, k_block, hd),
                         lambda b_, k_, ik: (b_, k_, ik, 0)),
            pl.BlockSpec((1, 1, k_block, hd),
                         lambda b_, k_, ik: (b_, k_, ik, 0)),
            # (B, 1, S) layout: the block's last two dims (1, k_block)
            # satisfy the TPU tiling rule at any slot count B
            pl.BlockSpec((1, 1, k_block), lambda b_, k_, ik: (b_, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b_, k_, ik: (b_, k_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, valid_mask[:, None, :])
    return out.reshape(b, 1, h, hd)
