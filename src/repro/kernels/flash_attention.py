"""Pallas TPU flash attention (GQA, causal/local/full).

Grid (B, KV, nq, nk): each program handles one (batch, kv-head) pair, one
query tile, one key tile; the last grid dim iterates sequentially so the
online-softmax state (m, l, acc) lives in VMEM scratch across key tiles.
Query heads sharing a kv head (G = H/KV) are folded into the tile's row
dimension so the score matmul is a single (G*Qb, hd) x (hd, Kb) MXU op.

Block skipping: key tiles strictly above the causal diagonal (or outside the
sliding-window band) are skipped with @pl.when -- this is where the kernel
beats the XLA reference path, which executes masked-out FLOPs (DESIGN §6).

Ragged (left-padded) batches: ``pad`` gives each row's left-pad key count;
``k_pos >= pad[b]`` folds into the in-kernel mask and key tiles that end
before ``pad[b]`` extend the @pl.when skip -- pad columns cost zero FLOPs,
not just zero weight.  Fully-masked query rows (the pad rows themselves)
come out as finite zeros via the l==0 guard in ``_finish``.

Sequence lengths need NOT be block multiples: the wrapper right-pads q/k/v
up to the tile grid (the same trick as ``ref.attention_blocked``) and
slices the result; a ``k_len`` bound masks the phantom key columns wherever
the causal mask alone would not (full/local kinds, padded K).

VMEM budget per program (f32): q tile G*Qb*hd + k/v tiles 2*Kb*hd + acc
G*Qb*hd + stats 2*G*Qb  ~= 6 MB at G=8, Qb=Kb=512, hd=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(*refs, kind: str, window: int, q_block: int, k_block: int,
            g: int, nk: int, scale: float, k_len: int, has_pad: bool):
    if has_pad:
        q_ref, k_ref, v_ref, pad_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        pad_ref = None
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    # the whole (B,) pad vector sits in SMEM; this row's count is a scalar
    pad_b = pad_ref[pl.program_id(0)] if pad_ref is not None else None

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * q_block
    k_start = ik * k_block
    # phantom key tiles (wrapper right-padding) are dropped statically
    tile_live = k_start < k_len
    if kind == "causal":
        relevant = tile_live & (k_start <= q_start + q_block - 1)
    elif kind == "local":
        relevant = (tile_live & (k_start <= q_start + q_block - 1)
                    & (k_start + k_block - 1 > q_start - window))
    else:
        relevant = tile_live
    if pad_ref is not None:
        # key tile entirely inside this row's left pad: skip it outright
        relevant = relevant & (k_start + k_block > pad_b)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].reshape(g * q_block, q_ref.shape[-1])
        k = k_ref[0, 0]                        # (Kb, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ()))) * scale   # (G*Qb, Kb)
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = q_start + jax.lax.rem(rows, q_block)
        k_pos = k_start + cols
        mask = None
        if kind != "full":
            mask = k_pos <= q_pos
            if kind == "local":
                mask = mask & (k_pos > q_pos - window)
        if k_len % k_block:          # static: wrapper right-padded K -- the
            bound = k_pos < k_len    # last live tile has phantom columns
            mask = bound if mask is None else mask & bound
        if pad_ref is not None:
            valid = k_pos >= pad_b
            mask = valid if mask is None else mask & valid
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            # fully-masked rows: every score sits at _NEG == m_new, so
            # exp(s - m_new) = 1 would weigh masked keys; zero them instead
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v.astype(jnp.float32),
                                 (((1,), (0,)), ((), ())))
        acc_ref[...] = alpha * acc_ref[...] + pv
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        out = (acc_ref[...] / l).reshape(1, 1, g, q_block, o_ref.shape[-1])
        o_ref[...] = out.astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, kind: str = "causal", window: int = 0,
                           q_block: int = 512, k_block: int = 512,
                           pad=None, interpret: bool = False):
    """q (B,Sq,H,hd), k/v (B,Sk,KV,hd) -> (B,Sq,H,hd).

    ``pad`` (B,) int32: per-row LEFT-pad key count for ragged batches --
    keys below ``pad[b]`` are masked out of row b (the serving engine's
    bucketed prompt widths).  Sq/Sk may be any length: non-block-multiple
    sequences are right-padded to the tile grid internally and sliced back.
    """
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    q_block = min(q_block, sq)
    k_block = min(k_block, sk)
    sq_pad = (-sq) % q_block
    sk_pad = (-sk) % k_block
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0), (0, 0)))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
    sq_p, sk_p = sq + sq_pad, sk + sk_pad
    nq, nk = sq_p // q_block, sk_p // k_block

    qr = q.reshape(b, sq_p, kv, g, hd).transpose(0, 2, 3, 1, 4)  # (B,KV,G,Sq,hd)
    kr = k.transpose(0, 2, 1, 3)                                 # (B,KV,Sk,hd)
    vr = v.transpose(0, 2, 1, 3)

    in_specs = [
        pl.BlockSpec((1, 1, g, q_block, hd),
                     lambda b_, k_, iq, ik: (b_, k_, 0, iq, 0)),
        pl.BlockSpec((1, 1, k_block, hd),
                     lambda b_, k_, iq, ik: (b_, k_, ik, 0)),
        pl.BlockSpec((1, 1, k_block, hd),
                     lambda b_, k_, iq, ik: (b_, k_, ik, 0)),
    ]
    operands = [qr, kr, vr]
    if pad is not None:
        operands.append(jnp.asarray(pad, jnp.int32))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    kernel = functools.partial(
        _kernel, kind=kind, window=window, q_block=q_block, k_block=k_block,
        g=g, nk=nk, scale=1.0 / (hd ** 0.5), k_len=sk, has_pad=pad is not None)

    out = pl.pallas_call(
        kernel,
        grid=(b, kv, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, q_block, hd),
                               lambda b_, k_, iq, ik: (b_, k_, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * q_block, hd), jnp.float32),
            pltpu.VMEM((g * q_block, 1), jnp.float32),
            pltpu.VMEM((g * q_block, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq_p, h, hd)
    return out[:, :sq] if sq_pad else out
