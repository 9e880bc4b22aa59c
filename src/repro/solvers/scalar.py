"""Scalar root-finding and 1-D minimization, vmap-friendly.

Both routines use fixed iteration counts (``lax.fori_loop``) so they can be
jitted, vmapped and nested inside other solvers without dynamic shapes.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

_INV_PHI = 0.6180339887498949  # 1/phi
_INV_PHI2 = 0.3819660112501051  # 1/phi^2


def _match_vma(x, *like):
    """``x`` marked as varying over every manual mesh axis that ``x`` or
    any of ``like`` varies over (a no-op outside ``jax.shard_map``).

    A loop carry must leave with the varying axes it entered with; a
    bracket built from replicated constants (``lo``, ``hi``) is updated
    from per-device values (``fn(mid)``), so it enters replicated and
    leaves varying unless it is cast up front.
    """
    axes = set().union(*(getattr(y, "vma", None) or jax.typeof(y).vma
                         for y in like))
    return jax.lax.pcast(x, tuple(sorted(axes - jax.typeof(x).vma)),
                         to="varying")


def bisect(fn: Callable, lo, hi, iters: int = 80, endpoint: str = "mid"):
    """Find a root of ``fn`` on [lo, hi] by bisection.

    Assumes ``fn(lo)`` and ``fn(hi)`` bracket a root (sign change). If they
    do not, the result converges to one of the endpoints, which is the
    correct behaviour for the monotone complementarity searches we use it
    for (e.g. a Lagrange-multiplier price that is 0 at an inactive
    constraint).

    ``endpoint`` selects what is returned from the final bracket:
    ``"mid"`` (default) the midpoint; ``"hi"`` the upper end — which, for
    a *decreasing step function* such as a discrete market-clearing
    excess, is guaranteed to sit on the ``fn ≤ 0`` side whenever the
    initial ``hi`` does (the midpoint can land on either side of the
    jump).
    """
    if endpoint not in ("mid", "hi"):
        raise ValueError(f"endpoint must be 'mid' or 'hi', got {endpoint!r}")
    lo = jnp.asarray(lo, dtype=jnp.float64)
    hi = jnp.asarray(hi, dtype=jnp.float64)
    f_type = jax.eval_shape(fn, lo)
    f_lo = jnp.zeros(f_type.shape, f_type.dtype)
    lo, hi, f_lo = (_match_vma(x, lo, hi, f_type) for x in (lo, hi, f_lo))

    # Step 0 evaluates fn(lo); steps 1..iters bisect. One loop, so the
    # program holds a single copy of ``fn``.
    def body(i, state):
        lo, hi, f_lo = state
        first = i == 0
        mid = 0.5 * (lo + hi)
        f_x = fn(jnp.where(first, lo, mid))
        go_right = jnp.sign(f_x) == jnp.sign(f_lo)
        new_lo = jnp.where(go_right & ~first, mid, lo)
        new_f_lo = jnp.where(first | go_right, f_x, f_lo)
        new_hi = jnp.where(go_right | first, hi, mid)
        return new_lo, new_hi, new_f_lo

    lo, hi, _ = jax.lax.fori_loop(0, iters + 1, body, (lo, hi, f_lo))
    return hi if endpoint == "hi" else 0.5 * (lo + hi)


def golden_section(fn: Callable, lo, hi, iters: int = 72):
    """Minimize a (quasi-)convex scalar ``fn`` on [lo, hi].

    Returns the argmin. 72 iterations shrink the bracket by
    ~phi^-72 ≈ 1e-15, i.e. to float64 resolution for O(1) intervals.
    """
    lo = jnp.asarray(lo, dtype=jnp.float64)
    hi = jnp.asarray(hi, dtype=jnp.float64)
    f_type = jax.eval_shape(fn, lo)
    a, b = _match_vma(lo, hi, f_type), _match_vma(hi, lo, f_type)

    def body(_, state):
        a, b = state
        h = b - a
        c = a + _INV_PHI2 * h
        d = a + _INV_PHI * h
        # Only one of (c, d) needs re-evaluation per iteration in the
        # classic scheme; evaluating both keeps the state static-shaped,
        # and one vmapped call of ``fn`` on the pair keeps the program to
        # a single copy of ``fn``.
        fc, fd = jax.vmap(fn)(jnp.stack([c, d]))
        shrink_right = fc < fd
        return jnp.where(shrink_right, a, c), jnp.where(shrink_right, d, b)

    a, b = jax.lax.fori_loop(0, iters, body, (a, b))
    return 0.5 * (a + b)


@partial(jax.jit, static_argnames=("fn", "grid"))
def minimize_grid_then_golden(fn: Callable, lo, hi, grid: int = 64):
    """Global-ish 1-D minimization: coarse grid to localize, then golden.

    Useful when ``fn`` is only piecewise-convex (e.g. clipped frequency
    requirement inside an energy expression).
    """
    lo = jnp.asarray(lo, dtype=jnp.float64)
    hi = jnp.asarray(hi, dtype=jnp.float64)
    xs = jnp.linspace(lo, hi, grid)
    vals = jax.vmap(fn)(xs)
    i = jnp.argmin(vals)
    cell = (hi - lo) / (grid - 1)
    a = jnp.clip(xs[i] - cell, lo, hi)
    b = jnp.clip(xs[i] + cell, lo, hi)
    return golden_section(fn, a, b)
