"""Pallas TPU kernel for the Mamba2 SSD (state-space dual) chunked scan.

Grid (B, H, nchunks): the last dim iterates chunks sequentially per
(batch, head); the running state M (N x P) lives in VMEM scratch across
chunk iterations — exactly the TPU-native reformulation of the SSD
recurrence: per chunk, the quadratic "attention-like" intra-chunk term is
two MXU matmuls (C·B^T weighted tri-matmul against X), and the inter-chunk
term applies the carried state.  This adapts Mamba2's GPU kernel (warp-level
scans) to the TPU memory hierarchy: chunk tiles in VMEM, state in VMEM
scratch, MXU for all O(Q^2)/O(QN) contractions (DESIGN §3/§6).

Reset support (ragged serving batches): an optional (B, S) mask zeroes the
carried state ENTERING the flagged steps.  Implemented with within-chunk
segment ids (cumsum of the reset column) in the LINEAR domain: the
triangular decay table is additionally masked to same-segment (q, r) pairs,
chunk-boundary contributions drop tokens with a later in-chunk reset, the
inter-chunk term is gated on "no reset yet", and the VMEM-carried M is
zeroed across any chunk containing a reset.  (A log-domain -inf reset would
be absorbed by the cumsum and corrupt every later same-segment decay.)

Semantics == repro.kernels.ref.ssd_scan_ref (the oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cumsum0(t):
    """Inclusive cumsum down axis 0 by log2(Q) roll-and-add doubling passes
    (Mosaic has no cumsum primitive)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    shift = 1
    while shift < t.shape[0]:
        t = t + jnp.where(rows >= shift, jnp.roll(t, shift, axis=0), 0)
        shift *= 2
    return t


def _kernel(*refs, nchunks: int, chunk: int, has_reset: bool):
    if has_reset:
        (x_ref, dt_ref, alog_ref, b_ref, c_ref, dskip_ref, reset_ref,
         y_ref, state_out_ref, m_ref) = refs
    else:
        (x_ref, dt_ref, alog_ref, b_ref, c_ref, dskip_ref,
         y_ref, state_out_ref, m_ref) = refs
        reset_ref = None
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        m_ref[...] = jnp.zeros_like(m_ref)

    x = x_ref[0, 0].astype(jnp.float32)         # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)       # (Q, 1) -> column
    bmat = b_ref[0, 0].astype(jnp.float32)      # (Q, N)
    cmat = c_ref[0, 0].astype(jnp.float32)      # (Q, N)
    # per-head scalars from the whole (H,) vectors held in SMEM
    ih = pl.program_id(1)
    a = -jnp.exp(jnp.full((1, 1), alog_ref[ih], jnp.float32))   # A_h
    d = dskip_ref[ih]

    la = a * dt                                  # (Q,1) log decay per step
    cum = _cumsum0(la)                           # (Q,1)
    total = cum[chunk - 1:chunk, :]              # (1,1)

    # intra-chunk triangular term (log-domain masked decay)
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())))  # (Q,Q)
    ldecay = cum - cum.T                         # (Q,Q) = cum_q - cum_r
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    keep = cols <= rows
    if reset_ref is not None:
        # seg[q] = #resets at in-chunk positions <= q; decay(q, r) survives
        # only when no reset lies in (r, q] -- i.e. seg_q == seg_r.
        seg = _cumsum0(reset_ref[0].astype(jnp.int32))   # (Q,1)
        keep = keep & (seg == seg.T)
    ldecay = jnp.where(keep, ldecay, -jnp.inf)
    w = scores * jnp.exp(ldecay)                 # (Q,Q)
    xdt = x * dt                                 # (Q,P)
    y_intra = jax.lax.dot_general(w, xdt, (((1,), (0,)), ((), ())))

    # inter-chunk term from carried state M (N,P)
    inter_decay = jnp.exp(cum)                   # (Q,1)
    if reset_ref is not None:
        inter_decay = jnp.where(seg == 0, inter_decay, 0.0)
    y_inter = inter_decay * jax.lax.dot_general(
        cmat, m_ref[...], (((1,), (0,)), ((), ())))

    y_ref[...] = ((y_intra + y_inter + d * x)[None, None]).astype(y_ref.dtype)

    # state update: M <- exp(total) M + sum_r exp(total-cum_r) dt_r b_r x_r^T
    decay_to_end = jnp.exp(total - cum)          # (Q,1)
    carry = jnp.exp(total)                       # (1,1)
    if reset_ref is not None:
        # tokens with a later in-chunk reset never reach the chunk boundary;
        # M itself survives the chunk only when the chunk has no reset.
        decay_to_end = jnp.where(seg == seg[chunk - 1:chunk, :],
                                 decay_to_end, 0.0)
        carry = jnp.where(seg[chunk - 1:chunk, :] == 0, carry, 0.0)
    contrib = jax.lax.dot_general(bmat * (decay_to_end * dt), x,
                                  (((0,), (0,)), ((), ())))   # (N,P)
    # (1,1) -> (N,1) through an add, then lane-broadcast: Mosaic broadcasts
    # along sublanes or lanes in one op, never both (a plain broadcast_to
    # chain folds back into one op)
    carry = jnp.zeros((m_ref.shape[0], 1), jnp.float32) + carry
    m_ref[...] = m_ref[...] * carry + contrib

    @pl.when(ic == nchunks - 1)
    def _finish():
        state_out_ref[...] = m_ref[...][None, None]


def ssd_scan_pallas(x, dt, a_log, b, c, d_skip, *, chunk: int,
                    reset=None, interpret: bool = False):
    """x (B,S,H,P), dt (B,S,H), a_log (H,), b/c (B,S,G,N), d_skip (H,).
    ``reset`` (B, S) bool: True zeroes the state entering step t.
    Returns (y (B,S,H,P), final_state (B,H,N,P))."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert s % chunk == 0
    nchunks = s // chunk
    reps = h // g
    bh = jnp.repeat(b, reps, axis=2)
    ch = jnp.repeat(c, reps, axis=2)

    xr = x.transpose(0, 2, 1, 3)          # (B,H,S,P)
    dtr = dt.transpose(0, 2, 1)[..., None]  # (B,H,S,1)
    br = bh.transpose(0, 2, 1, 3)
    cr = ch.transpose(0, 2, 1, 3)

    in_specs = [
        pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
        pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, c_: (b_, h_, c_, 0)),
        pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, c_: (b_, h_, c_, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    f32 = lambda t: t.astype(jnp.float32)
    operands = [xr, dtr, f32(a_log), br, cr, f32(d_skip)]
    if reset is not None:
        operands.append(reset.astype(jnp.float32)[:, :, None])   # (B,S,1)
        in_specs.append(pl.BlockSpec((1, chunk, 1),
                                     lambda b_, h_, c_: (b_, c_, 0)))

    kernel = functools.partial(_kernel, nchunks=nchunks, chunk=chunk,
                               has_reset=reset is not None)
    y, state = pl.pallas_call(
        kernel,
        grid=(bsz, h, nchunks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return y.transpose(0, 2, 1, 3), state
