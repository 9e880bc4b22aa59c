"""Levenberg–Marquardt nonlinear least squares (pure JAX).

Used to fit the paper's mean-inference-time model  t̄(f) = w / (g · f)
(eq. (10)) — and any other small regression — from measured data, exactly
as Section IV-A fits Fig. 6 with "the nonlinear least squares method".
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class LMResult(NamedTuple):
    params: jnp.ndarray
    residual_norm_sq: jnp.ndarray  # squared 2-norm of residuals (paper's metric)
    iterations: jnp.ndarray


def levenberg_marquardt(
    residual_fn: Callable[[jnp.ndarray], jnp.ndarray],
    x0: jnp.ndarray,
    iters: int = 60,
    lam0: float = 1e-3,
    lam_up: float = 10.0,
    lam_down: float = 0.5,
) -> LMResult:
    """Minimize ``0.5 * ||residual_fn(x)||^2`` with LM damping.

    Fixed-iteration trust-region-flavoured LM: a step is accepted when it
    decreases the residual norm, otherwise the damping is increased and the
    step rejected. Jit- and vmap-safe.
    """
    x0 = jnp.asarray(x0, dtype=jnp.float64)

    def loss(x):
        r = residual_fn(x)
        return 0.5 * jnp.sum(r * r)

    def body(_, state):
        x, lam, f_x = state
        r = residual_fn(x)
        J = jax.jacfwd(residual_fn)(x)
        g = J.T @ r
        H = J.T @ J + lam * jnp.eye(x.shape[0], dtype=x.dtype)
        step = jax.scipy.linalg.cho_solve(  # JᵀJ + λI is SPD
            jax.scipy.linalg.cho_factor(H), -g)
        x_new = x + step
        f_new = loss(x_new)
        accept = f_new < f_x
        x = jnp.where(accept, x_new, x)
        f_x = jnp.where(accept, f_new, f_x)
        lam = jnp.where(accept, lam * lam_down, lam * lam_up)
        lam = jnp.clip(lam, 1e-12, 1e12)
        return x, lam, f_x

    x, _, f_x = jax.lax.fori_loop(
        0, iters, body, (x0, jnp.asarray(lam0, jnp.float64), loss(x0))
    )
    return LMResult(params=x, residual_norm_sq=2.0 * f_x, iterations=jnp.asarray(iters))


def fit_inverse_frequency(freqs: jnp.ndarray, times: jnp.ndarray) -> LMResult:
    """Fit the paper's model  t̄ = a / f  (a = w/g) to (frequency, time) data.

    Returns a 1-parameter LM fit. ``w`` (GFLOPs) is known from the model's
    cost table, so ``g = w / a``.
    """
    freqs = jnp.asarray(freqs, jnp.float64)
    times = jnp.asarray(times, jnp.float64)

    def residual(params):
        (a,) = params
        return a / freqs - times

    # init from the median of t*f (exact if the model holds).
    a0 = jnp.median(times * freqs)
    return levenberg_marquardt(residual, jnp.array([a0]))
