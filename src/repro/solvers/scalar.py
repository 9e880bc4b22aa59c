"""Scalar root-finding and 1-D minimization, vmap-friendly.

Both routines use fixed iteration counts (``lax.fori_loop``) so they can be
jitted, vmapped and nested inside other solvers without dynamic shapes.
"""
from __future__ import annotations

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

    Returns the midpoint of the final bracket. ``iters`` shrinks, each
    by 1/phi, take the bracket to ~phi^-72 ≈ 1e-15 of its width, i.e. to
    float64 resolution for O(1) intervals.

    The classic scheme: the interior point kept by a shrink is the other
    point of the new bracket, so each shrink evaluates ``fn`` once, and
    the carry holds both interior points and their values. Steps 0 and 1
    evaluate the first two points, steps 2..iters+1 shrink, so ``fn`` is
    evaluated ``iters + 2`` times at one call site, which keeps the
    program to a single copy of ``fn``.
    """
    lo = jnp.asarray(lo, dtype=jnp.float64)
    hi = jnp.asarray(hi, dtype=jnp.float64)
    f_type = jax.eval_shape(fn, lo)
    f0 = jnp.zeros(f_type.shape, f_type.dtype)
    h = hi - lo
    a, b, c, d, f_c, f_d = (_match_vma(x, lo, hi, f_type) for x in (
        lo, hi, lo + _INV_PHI2 * h, lo + _INV_PHI * h, f0, f0))

    def body(i, state):
        a, b, c, d, f_c, f_d = state
        shrink = i >= 2
        # Keep [a, d] (c becomes the new d) or [c, b] (d becomes the new
        # c); the new point sits at the golden cut of the new bracket.
        c_wins = f_c < f_d
        left, right = shrink & c_wins, shrink & ~c_wins
        a = jnp.where(right, c, a)
        b = jnp.where(left, d, b)
        h = b - a
        c, d = (jnp.where(left, a + _INV_PHI2 * h, jnp.where(right, d, c)),
                jnp.where(left, c, jnp.where(right, a + _INV_PHI * h, d)))
        f_c, f_d = jnp.where(right, f_d, f_c), jnp.where(left, f_c, f_d)
        at_c = (i == 0) | left
        f_x = fn(jnp.where(at_c, c, d))
        return a, b, c, d, jnp.where(at_c, f_x, f_c), jnp.where(at_c, f_d, f_x)

    a, b, *_ = jax.lax.fori_loop(0, iters + 2, body, (a, b, c, d, f_c, f_d))
    return 0.5 * (a + b)

