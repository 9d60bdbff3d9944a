"""Per-slot convex resource allocation (paper Sec. IV-C).

Given the partitioning decision ``cut`` (from the DRL policy), the remaining
continuous allocation decouples into three convex programs, solved exactly and
jit-compatibly:

* P3 (eq. 19)  local CPU frequency  f_ue  -- bisection on the slope's sign per UE
               (the paper uses a Fibonacci search; see ``solve_p3``)
* P4 (eq. 20)  edge CPU frequency   f_es  -- closed-form KKT water-filling (eq. 23)
* P5 (eq. 24)  uplink bandwidth     alpha -- two-level KKT bisection (replaces CVX;
               see DESIGN.md "Hardware adaptation")

All solvers are fixed-iteration (`lax.fori_loop`) so they lower to TPU and
vectorize over UEs.  Log-domain comparisons keep P5 stable in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-12

# ---------------------------------------------------------------------------
# P3: local computational resource (eq. 19)
# ---------------------------------------------------------------------------

_P3_ITERS = 40


def p3_objective(f, q_energy, kappa, d_ue, lam, v):
    """Eq. (19): Q*kappa*f^2*d*lam + V*(d/f + d^2 lam / (2 (f^2 - f d lam)))."""
    f = jnp.maximum(f, _EPS)
    energy = q_energy * kappa * jnp.square(f) * d_ue * lam
    proc = d_ue / f
    denom = jnp.maximum(jnp.square(f) - f * d_ue * lam, _EPS)
    queue = jnp.square(d_ue) * lam / (2.0 * denom)
    return energy + v * (proc + queue)


def solve_p3(q_energy, kappa, d_ue, lam, v, f_max, *, stability_margin=1e-3):
    """Minimizer of (19) per UE on [d*lam*(1+margin)+1, f_max].

    (19) is convex there, so the minimizer is where its slope changes sign:
    bisection on that sign.  The paper's Fibonacci search on the objective
    VALUES pins f only to ~sqrt(eps) relative in float32 -- (19) is flat at
    its minimum, so rounding noise decides the comparisons -- and two
    compilations of the same step (a wider cell batch, another fusion)
    then disagreed by 1e-5 in delay and energy.  The slope crosses zero
    with nonzero gradient, so its sign pins f to a few ulps.

    Vectorized over leading UE axis.  UEs with ``d_ue == 0`` (full offload)
    get f_ue = 0.  The caller guarantees feasibility ``d*lam < f_max`` (C7,
    enforced by action projection); if violated we clamp to f_max.
    """
    lo = d_ue * lam * (1.0 + stability_margin) + 1.0
    hi = jnp.full_like(lo, f_max)
    lo = jnp.minimum(lo, hi)
    dl = d_ue * lam

    def slope(f):
        # f^2 * d(19)/df: same sign, and no f^4 term to overflow float32
        r = d_ue / jnp.maximum(f - dl, _EPS)
        return ((2.0 * q_energy * kappa * dl * f) * f * f - v * d_ue
                - 0.5 * v * lam * r * r * (2.0 * f - dl))

    def body(_, ab):
        a, b = ab
        mid = 0.5 * (a + b)
        rising = slope(mid) > 0
        return jnp.where(rising, a, mid), jnp.where(rising, mid, b)

    a, b = jax.lax.fori_loop(0, _P3_ITERS, body, (lo, hi))
    return jnp.where(d_ue > 0, 0.5 * (a + b), 0.0)


# ---------------------------------------------------------------------------
# P4: edge computational resource (closed form, eq. 23)
# ---------------------------------------------------------------------------

def solve_p4(d_es, f_max_es):
    """f_es* = f_max * sqrt(d_n) / sum_m sqrt(d_m)  (eq. 23).

    UEs with no edge portion receive 0 (sqrt(0) = 0 drops them naturally).
    If nobody offloads, return zeros.
    """
    root = jnp.sqrt(jnp.maximum(d_es, 0.0))
    total = jnp.sum(root)
    safe_total = jnp.where(total > 0, total, 1.0)
    return jnp.where(total > 0, f_max_es * root / safe_total, 0.0)


# ---------------------------------------------------------------------------
# P5: communication resource (two-level KKT bisection, eq. 24)
# ---------------------------------------------------------------------------

_ALPHA_MIN = 1e-7
# Bisection depths sized for float32: 2^-36 on alpha in [1e-7, 1] and
# 2^-42 on log-eta in [-80, 80] are both far below f32 resolution already;
# deeper loops were pure sequential overhead (the solver runs inside every
# per-slot step, and the batched multi-cell path pays per-iteration cost).
_INNER_ITERS = 36
_OUTER_ITERS = 42


def _log_rate_terms(alpha, s):
    """r(a) = a*log2(1+s/a); returns (log r, log r') computed stably."""
    a = jnp.maximum(alpha, _ALPHA_MIN * 1e-3)
    l2 = jnp.log2(1.0 + s / a)
    log_r = jnp.log(a) + jnp.log(jnp.maximum(l2, _EPS))
    # r'(a) = log2(1+s/a) - s / (ln2 * (a + s))  > 0
    rp = l2 - s / (jnp.log(2.0) * (a + s))
    log_rp = jnp.log(jnp.maximum(rp, _EPS))
    return log_r, log_rp


def _log_marginal(alpha, s, log_c):
    """log of m(a) = c * r'(a) / r(a)^2 -- the (negated) objective slope."""
    log_r, log_rp = _log_rate_terms(alpha, s)
    return log_c + log_rp - 2.0 * log_r


def solve_p5(q_energy, p_tx, lam, v, psi_bytes, w_hz, gain, n0):
    """Minimize eq. (24) s.t. sum(alpha) <= 1, alpha >= 0.

    KKT: the marginal m_n(alpha_n) is equalized across UEs with psi > 0 and
    the bandwidth constraint is tight.  m is strictly decreasing (convexity),
    so: inner bisection inverts m_n at a trial multiplier eta, outer bisection
    drives sum(alpha(eta)) -> 1.  Runs entirely in log domain.
    """
    bits = 8.0 * psi_bytes
    active = bits > 0
    n_active = jnp.sum(active)
    s = p_tx * gain / (w_hz * n0)                     # per-UE SNR coefficient
    coeff = (q_energy * p_tx * lam + v) * bits / w_hz  # c_n in DESIGN notation

    coeff_c = jnp.maximum(coeff, _EPS)
    ln2 = jnp.log(2.0)
    # Inner bisection runs in u-space, u = ln(1 + s/alpha) (monotone
    # DECREASING in alpha), because there r and r' are arithmetic in
    # (u, e^-u):  r = a*u/ln2,  r' = (u - (1 - e^-u))/ln2,  a = s*e^-u/(1-e^-u).
    # That leaves ONE transcendental (expm1) per bisection step -- the a-space
    # form needs a log2 per step, and scalar libm calls are what the solver's
    # wall time is made of once many cells are batched.
    u_lo0 = jnp.log1p(s)                  # alpha = 1
    u_hi0 = jnp.log1p(s / _ALPHA_MIN)     # alpha = ALPHA_MIN

    def alpha_of_eta(log_eta):
        # m(a) > eta  <=>  c * r'(a) > eta * r(a)^2, all in linear domain;
        # magnitudes stay in f32 range for |log_eta| <= 40 (m spans
        # ~e^-35..e^38 at the parameter extremes).
        eta = jnp.exp(log_eta)

        def a_of_u(u):
            em = -jnp.expm1(-u)           # 1 - e^-u, stable for small u
            return s * (1.0 - em) / jnp.maximum(em, _EPS), em

        def inner(_, uu):
            u_lo, u_hi = uu
            mid = 0.5 * (u_lo + u_hi)
            a, em = a_of_u(mid)
            # c * rp > eta * r^2  <=>  c*ln2*(u - em) > eta * a^2 * u^2
            too_steep = (coeff_c * ln2 * jnp.maximum(mid - em, _EPS)
                         > eta * a * a * mid * mid)
            # m(a) > eta -> alpha* > a -> u* < mid
            return jnp.where(too_steep, u_lo, mid), jnp.where(too_steep, mid, u_hi)

        u_lo, u_hi = jax.lax.fori_loop(0, _INNER_ITERS, inner, (u_lo0, u_hi0))
        alpha, _ = a_of_u(0.5 * (u_lo + u_hi))
        return jnp.where(active, jnp.clip(alpha, _ALPHA_MIN, 1.0), 0.0)

    def outer(_, bounds):
        e_lo, e_hi = bounds
        mid = 0.5 * (e_lo + e_hi)
        total = jnp.sum(alpha_of_eta(mid))
        # sum(alpha) decreasing in eta: too much bandwidth -> raise eta.
        over = total > 1.0
        return jnp.where(over, mid, e_lo), jnp.where(over, e_hi, mid)

    e_lo, e_hi = jax.lax.fori_loop(
        0, _OUTER_ITERS, outer,
        (jnp.asarray(-40.0, s.dtype), jnp.asarray(40.0, s.dtype)))
    alpha = alpha_of_eta(0.5 * (e_lo + e_hi))
    # Exactness: single active UE -> alpha = 1; none -> zeros.
    alpha = jnp.where(n_active == 1, jnp.where(active, 1.0, 0.0), alpha)
    # Normalize residual bisection slack onto active UEs.
    total = jnp.sum(alpha)
    alpha = jnp.where(n_active > 0, alpha / jnp.maximum(total, _EPS), 0.0)
    return alpha


def p5_objective(alpha, q_energy, p_tx, lam, v, psi_bytes, w_hz, gain, n0):
    """Eq. (24) objective value (for tests / oracle search)."""
    from .queueing import trans_delay

    t = trans_delay(psi_bytes, alpha, w_hz, p_tx, gain, n0)
    return jnp.sum((q_energy * p_tx * lam + v) * t)
