"""Pallas TPU kernel for the LyMDO partition sweep (paper eq. 11 over every
(UE, cut) pair) -- the controller's dense hot spot (DESIGN §6).

TPU adaptation of the paper's per-slot search:
  * layer prefix sums  -> one (C x C) upper-triangular ones matmul on the MXU
    (instead of a serial scan),
  * running activation maxima -> log2(C) doubling passes on the VPU,
  * the P3 Fibonacci line search -> 40 data-parallel iterations over the
    whole (UE-block x cut) tile at once,
so evaluating ALL cuts costs two small matmuls + elementwise work, and the
argmin over cuts (the Oracle policy / PPO action pruning) reads one table.

Oracle semantics == repro.kernels.ref.partition_sweep_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_BIG = 1e30
_FIB_ITERS = 40


def _fib_ratios():
    fib = np.ones(_FIB_ITERS + 3)
    for i in range(2, _FIB_ITERS + 3):
        fib[i] = fib[i - 1] + fib[i - 2]
    lo = np.array([fib[_FIB_ITERS - k] / fib[_FIB_ITERS - k + 2]
                   for k in range(_FIB_ITERS)], np.float32)
    hi = np.array([fib[_FIB_ITERS - k + 1] / fib[_FIB_ITERS - k + 2]
                   for k in range(_FIB_ITERS)], np.float32)
    return lo, hi


_RLO, _RHI = _fib_ratios()


def _kernel(macs_ref, params_ref, acts_ref, psi_ref, l_ref, lam_ref,
            gain_ref, qe_ref, qm_ref, out_ref, *, c: int, n_total: int,
            rho, kappa, p_tx, w_hz, n0, f_max_ue, f_max_es, v,
            gamma_ue, gamma_es, stability_margin):
    macs = macs_ref[...].astype(jnp.float32)        # (Nb, C)
    params = params_ref[...].astype(jnp.float32)
    acts = acts_ref[...].astype(jnp.float32)
    psi = psi_ref[...].astype(jnp.float32)
    l_n = l_ref[...].astype(jnp.float32)            # (Nb, 1)
    lam = lam_ref[...].astype(jnp.float32)
    gain = gain_ref[...].astype(jnp.float32)
    qe = qe_ref[...].astype(jnp.float32)
    qm = qm_ref[...].astype(jnp.float32)

    # Mosaic builds integer iotas only: make them int32, then cast
    iota = lambda shape, dim: jax.lax.broadcasted_iota(
        jnp.int32, shape, dim).astype(jnp.float32)
    cols = iota(macs.shape, 1)
    in_range = cols <= l_n                          # valid cuts per UE

    # -- prefix sums via upper-triangular ones matmul (MXU) ------------------
    rows_t = iota((c, c), 0)
    cols_t = iota((c, c), 1)
    tri = (rows_t <= cols_t).astype(jnp.float32)    # T[j,c] = 1 iff j <= c
    prefix_macs = jax.lax.dot_general(macs, tri, (((1,), (0,)), ((), ())))
    prefix_params = jax.lax.dot_general(params, tri, (((1,), (0,)), ((), ())))
    total_macs = prefix_macs[:, c - 1:c]
    total_params = prefix_params[:, c - 1:c]
    suffix_macs = total_macs - prefix_macs
    suffix_params = total_params - prefix_params

    # -- running activation maxima via doubling (VPU) ------------------------
    acts_m = jnp.where((cols >= 1.0) & in_range, acts, 0.0)
    pmax = acts_m
    shift = 1
    while shift < c:
        prev = jnp.roll(pmax, shift, axis=1)
        prev = jnp.where(cols >= shift, prev, 0.0)
        pmax = jnp.maximum(pmax, prev)
        shift *= 2
    smax_incl = acts_m
    shift = 1
    while shift < c:
        nxt = jnp.roll(smax_incl, -shift, axis=1)
        nxt = jnp.where(cols < c - shift, nxt, 0.0)
        smax_incl = jnp.maximum(smax_incl, nxt)
        shift *= 2
    smax = jnp.where(cols < c - 1, jnp.roll(smax_incl, -1, axis=1), 0.0)

    # -- per-cut demands ------------------------------------------------------
    d_ue = rho * prefix_macs
    d_es = rho * suffix_macs

    # -- P3 Fibonacci search over the whole tile -----------------------------
    lo = d_ue * lam * (1.0 + stability_margin) + 1.0
    hi = jnp.full_like(lo, f_max_ue)
    lo = jnp.minimum(lo, hi)

    def obj(f):
        f = jnp.maximum(f, 1e-12)
        energy = qe * kappa * f * f * d_ue * lam
        proc = d_ue / f
        denom = jnp.maximum(f * f - f * d_ue * lam, 1e-12)
        queue = d_ue * d_ue * lam / (2.0 * denom)
        return energy + v * (proc + queue)

    a_, b_ = lo, hi
    for k in range(_FIB_ITERS):
        span = b_ - a_
        x1 = a_ + _RLO[k] * span
        x2 = a_ + _RHI[k] * span
        take_left = obj(x1) < obj(x2)
        a_ = jnp.where(take_left, a_, x1)
        b_ = jnp.where(take_left, x2, b_)
    f_ue = 0.5 * (a_ + b_)
    f_ue = jnp.where(obj(hi) < obj(f_ue), hi, f_ue)
    f_ue = jnp.where(d_ue > 0, f_ue, 0.0)

    # -- delays ---------------------------------------------------------------
    mu = jnp.where(d_ue > 0, f_ue / jnp.maximum(d_ue, 1e-12), 1e30)
    wait = lam / (2.0 * mu * jnp.maximum(mu - lam, 1e-12))
    t_ue = jnp.where(d_ue > 0, 1.0 / mu + wait, 0.0)

    alpha = 1.0 / n_total
    snr = p_tx * gain / (alpha * w_hz * n0)
    rate = alpha * w_hz * (jnp.log(1.0 + snr) / jnp.log(2.0))
    t_tx = jnp.where(psi > 0, 8.0 * psi / jnp.maximum(rate, 1e-12), 0.0)

    f_es = f_max_es / n_total
    t_es = jnp.where(d_es > 0, d_es / f_es, 0.0)

    # -- energy / memory / objective -----------------------------------------
    energy = (kappa * f_ue * f_ue * d_ue * lam) + p_tx * t_tx * lam
    mem = (gamma_ue * prefix_params + pmax
           + gamma_es * suffix_params + smax) / 1e9
    objv = qe * energy + qm * mem + v * (t_ue + t_tx + t_es)

    feasible = in_range & (d_ue * lam * (1.0 + stability_margin) < f_max_ue)
    out_ref[...] = jnp.where(feasible, objv, _BIG)


def partition_sweep_pallas(macs, params_b, acts, psi, L, lam, gain, q_energy,
                           q_memory, scalars, *, ue_block: int = 8,
                           interpret: bool = False, n_total: int | None = None):
    """All args (N, C) / (N,); scalars: dict of MEC constants.
    Returns the (N, C) objective table (infeasible cells = 1e30).

    ``n_total`` overrides the UE count used for the even-split decoupling
    (alpha = 1/n_total, f_es = f_max_es/n_total).  It defaults to N, but a
    batched caller that flattens B independent cells of N UEs each into one
    (B*N, C) problem must pass the per-cell N so the splits stay per-cell
    (see ``partition_sweep_batched``).
    """
    n, c = macs.shape
    if n_total is None:
        n_total = n
    pad = (-n) % ue_block
    if pad:
        padded = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        macs, params_b, acts, psi = map(padded, (macs, params_b, acts, psi))
        L, lam, gain = map(padded, (L, lam, gain))
        q_energy, q_memory = map(padded, (q_energy, q_memory))
    nb = macs.shape[0] // ue_block

    col = lambda t: t.reshape(-1, 1).astype(jnp.float32)
    kernel = functools.partial(
        _kernel, c=c, n_total=n_total,
        rho=scalars["rho"], kappa=scalars["kappa"], p_tx=scalars["p_tx"],
        w_hz=scalars["w_hz"], n0=scalars["n0"],
        f_max_ue=scalars["f_max_ue"], f_max_es=scalars["f_max_es"],
        v=scalars["v"], gamma_ue=scalars["gamma_ue"],
        gamma_es=scalars["gamma_es"],
        stability_margin=scalars.get("stability_margin", 1e-3))

    row_spec = pl.BlockSpec((ue_block, c), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((ue_block, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[row_spec, row_spec, row_spec, row_spec,
                  vec_spec, vec_spec, vec_spec, vec_spec, vec_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((macs.shape[0], c), jnp.float32),
        interpret=interpret,
    )(macs.astype(jnp.float32), params_b.astype(jnp.float32),
      acts.astype(jnp.float32), psi.astype(jnp.float32),
      col(L), col(lam), col(gain), col(q_energy), col(q_memory))
    return out[:n]


def partition_sweep_batched(macs, params_b, acts, psi, L, lam, gain, q_energy,
                            q_memory, scalars, *, ue_block: int = 8,
                            interpret: bool = False):
    """Batched sweep over B independent cells in ONE kernel launch.

    Tables are (B, N, C), vectors (B, N); scalars are shared across cells
    (they are baked into the kernel as compile-time constants).  The B*N UE
    rows are flattened onto the kernel's UE-block grid -- cells never
    interact row-wise, and the even-split decoupling stays per-cell via
    ``n_total=N``.  Returns the (B, N, C) objective table.
    """
    b, n, c = macs.shape
    flat = lambda t: t.reshape((b * n,) + t.shape[2:])
    out = partition_sweep_pallas(
        flat(macs), flat(params_b), flat(acts), flat(psi),
        flat(L), flat(lam), flat(gain), flat(q_energy), flat(q_memory),
        scalars, ue_block=ue_block, interpret=interpret, n_total=n)
    return out.reshape(b, n, c)
