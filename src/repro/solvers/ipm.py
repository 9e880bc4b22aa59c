"""Equality-constrained log-barrier interior-point method (pure JAX).

This is the workhorse behind both paper subproblems:

* the resource-allocation problem (23) — convex, solved to optimality
  (the paper prescribes "an interior point (IPT) algorithm"), and
* the inner convex approximations (36) of the PCCP loop (Algorithm 1).

Two solve paths share the barrier/Newton/line-search skeleton:

- ``barrier_solve`` on a :class:`BarrierSpec` — the **dense autodiff**
  path: ``jax.hessian`` of the barrier plus a dense Cholesky KKT
  elimination per Newton step. Fully generic (any smooth convex
  ``inequalities`` callable); this is what ``resource.allocate_ipm``
  needs, whose deadline rows are non-affine in the bandwidth (t_off =
  d/R(b) with a log-rate). Kept as the A/B reference for the PCCP.
- ``structured_barrier_solve`` on a :class:`StructuredSpec` — the
  **structure-exploiting** path for programs of the exact family the
  PCCP inner problem (36) belongs to: affine constraints plus a few
  diagonal-quadratic rows, ``fi(z) = C z + c0 + q(z)``. Gradient and
  Hessian are closed-form (no autodiff jaxpr blow-up at compile time),
  the Hessian is solved in O(n) by pair elimination + Sherman–Morrison–
  Woodbury on its ``D + U S Uᵀ`` decomposition (no O(n³) Cholesky), and
  the backtracking line search updates all candidates analytically from
  one precomputed ``C dz`` matvec (DESIGN.md §solver).

Design notes
------------
- Fixed iteration *bounds* everywhere (``lax.fori_loop`` /
  ``lax.while_loop`` with a trip cap) so the solvers jit once and vmap
  across devices/problems. ``gate_tol`` enables a Newton-decrement early
  exit: λ²/2 below a tolerance relative to the current barrier value
  means the remaining steps cannot move the iterate, so the stage stops
  (under ``vmap`` the batched while_loop keeps stepping until every lane
  is done — the exit saves wall-clock only when the whole batch
  converges, which is the common case late in the barrier ramp).
- Newton steps solve the KKT system  [H Aᵀ; A 0] [dz; ν] = [-∇φ; 0]
  with **scale-aware** Tikhonov regularization on H (relative to
  ``max(diag H)`` — the PCCP's ρ-penalty ramp scales the barrier Hessian
  over ~6 orders of magnitude, where any fixed absolute jitter is either
  inert or dominant); equality feasibility (A z = b) is maintained
  exactly from a feasible start.
- Backtracking line search enforces *strict* inequality feasibility before
  evaluating the barrier (log of a non-positive argument is NaN and NaN
  comparisons would silently accept bad steps — we check explicitly).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: Backtracking candidates s = 2⁻ᵏ, k < _LS_CANDIDATES. The smallest step
#: tried is 2⁻²³ ≈ 1e-7 — steps below that make no numerical progress on
#: the float64 barrier, and each extra candidate costs a batched function
#: evaluation in the planner's hot loop.
_LS_CANDIDATES = 24

#: Scale-aware Tikhonov: H + reg·I with reg = _REG_REL · max(diag H).
#: The barrier Hessian's scale ramps with the barrier parameter t and the
#: PCCP penalty ρ (diag entries span ~1 → 1e12 across a solve); a
#: *relative* jitter keeps the conditioning of the regularized system
#: constant across the ramp, where the seed's fixed ``reg = 1e-10`` was
#: dominant early and inert late.
_REG_REL = 1e-12

#: Newton-decrement gate: a stage stops once λ²/2 ≤ gate · (1 + |φ|).
#: λ²/2 bounds the remaining decrease of the self-concordant barrier, so
#: at 1e-13 relative the remaining steps are numerical noise.
_GATE_TOL = 1e-13


class BarrierSpec(NamedTuple):
    """A smooth convex program: min f0(z) s.t. fi(z) <= 0, A z = b."""

    objective: Callable[[jnp.ndarray], jnp.ndarray]
    inequalities: Callable[[jnp.ndarray], jnp.ndarray]
    eq_matrix: Optional[jnp.ndarray] = None  # (p, n)
    eq_rhs: Optional[jnp.ndarray] = None  # (p,)


class StructuredSpec(NamedTuple):
    """A linear program with affine + diagonal-quadratic inequalities:

        min c_obj·z   s.t.   fi(z) = C z + c0 + q(z) ≤ 0,   a·z = a_rhs,

    where ``q`` adds ``z·(quad_diag[k] ⊙ z)`` (a *diagonal* PSD quadratic)
    to row ``quad_rows[k]`` — the DC rows (36c)/(36d) of the PCCP inner
    problem are exactly this shape.

    The last six fields are **static structure metadata** (concrete numpy
    index arrays, fixed by the constraint layout — never traced values).
    They classify the rows of the constraint Jacobian ``G`` (= ``C`` plus
    the quadratic gradient corrections) for the closed-form Hessian

        H = Σ_i G_i G_iᵀ / u_i² + Σ_k (2/u_k) diag(quad_diag[k]),  u = −fi:

    - ``diag_rows``/``diag_cols`` — rows with a single nonzero (box and
      positivity rows): pure diagonal contributions.
    - ``pair_rows``/``pair_x``/``pair_elim`` — rows with exactly two
      nonzeros, at ``(pair_x[i], pair_elim[i])``: 2×2 blocks. Each
      ``pair_elim`` column may appear ONLY in its pair row and in diag
      rows (and must be absent from ``eq_vec``), so it is eliminated
      analytically by one Schur step.
    - ``dense_rows`` — everything else: the low-rank ``U S Uᵀ`` part,
      solved by Sherman–Morrison–Woodbury with a
      ``len(dense_rows)²``-sized inner system.

    The quadratic rows' Hessian corrections are diagonal by construction,
    but their Jacobian rows (``C`` row + ``2 q ⊙ z``) are not — every
    ``quad_rows`` entry must therefore also appear in ``dense_rows``
    (validated at trace time).
    """

    c_obj: jnp.ndarray  # (n,)
    C: jnp.ndarray  # (m, n)
    c0: jnp.ndarray  # (m,)
    quad_diag: jnp.ndarray  # (k_q, n) diagonal PSD coefficients
    eq_vec: Optional[jnp.ndarray] = None  # (n,) single equality row
    eq_rhs: Optional[jnp.ndarray] = None  # scalar
    # -- static structure metadata (concrete numpy, not traced) --
    quad_rows: np.ndarray = np.zeros((0,), np.int64)  # (k_q,)
    diag_rows: np.ndarray = np.zeros((0,), np.int64)
    diag_cols: np.ndarray = np.zeros((0,), np.int64)
    pair_rows: np.ndarray = np.zeros((0,), np.int64)
    pair_x: np.ndarray = np.zeros((0,), np.int64)
    pair_elim: np.ndarray = np.zeros((0,), np.int64)
    dense_rows: np.ndarray = np.zeros((0,), np.int64)


class BarrierResult(NamedTuple):
    z: jnp.ndarray
    objective: jnp.ndarray
    max_violation: jnp.ndarray  # max fi(z); <= 0 means feasible
    duality_gap_bound: jnp.ndarray  # m / t at the final barrier stage
    #: fail-soft flag (DESIGN.md §robustness): False when the returned
    #: iterate or objective went non-finite — the line searches reject
    #: NaN/∞ *candidates* (a NaN Armijo comparison is False, so the step
    #: is refused and the stage stops at the incumbent), but a poisoned
    #: *input* spec can still surface here. Callers treat ok=False as
    #: "discard this solve", not "crash".
    ok: jnp.ndarray = jnp.bool_(True)  # analyze: ok(TRC005): tiny scalar NamedTuple default; concrete bool stamp is the contract


# ---------------------------------------------------------------------------
# Structured-path building blocks (closed-form, no autodiff)
# ---------------------------------------------------------------------------


def structured_inequalities(spec: StructuredSpec, z: jnp.ndarray) -> jnp.ndarray:
    """fi(z) = C z + c0 + q(z) — one matvec plus the quadratic rows."""
    fi = spec.C @ z + spec.c0
    if spec.quad_rows.size:
        qz = jnp.sum(spec.quad_diag * (z * z)[None, :], axis=-1)
        fi = fi.at[spec.quad_rows].add(qz)
    return fi


def structured_objective(spec: StructuredSpec, z: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(spec.c_obj, z)


def structured_barrier(spec: StructuredSpec, z: jnp.ndarray, t) -> jnp.ndarray:
    """φ(z) = t·c_obj·z − Σ log(−fi). Reference implementation: the
    closed-form gradient/Hessian below are property-tested against
    ``jax.grad``/``jax.hessian`` of this function."""
    fi = structured_inequalities(spec, z)
    return t * structured_objective(spec, z) - jnp.sum(jnp.log(-fi))


def _structured_parts(spec: StructuredSpec, z: jnp.ndarray, t):  # analyze: ok(TRC002): StructuredSpec index metadata is concrete numpy by construction (trace-time shapes)
    """Closed-form barrier derivatives, decomposed by row class.

    Returns ``(fi, g, d, h, U, wd)`` with the Hessian of φ as

        H = diag(d) + Σ_i h_i (e_{xᵢ} e_{eᵢ}ᵀ + e_{eᵢ} e_{xᵢ}ᵀ) + U diag(wd) Uᵀ

    where (xᵢ, eᵢ) = (pair_x[i], pair_elim[i]); ``d`` already contains the
    pair rows' own diagonal entries, so only the off-diagonal couplings
    ``h`` ride separately.
    """
    # Static invariant (checked here, at trace time, so every entry point
    # — solver, grad, Hessian — enforces it): a quadratic row's Jacobian
    # is dense-ish (C row + 2 q⊙z), so it MUST be classified dense —
    # listing it as a diag/pair row would silently drop its G_i G_iᵀ/u²
    # outer product from the Hessian.
    if not np.isin(spec.quad_rows, spec.dense_rows).all():
        raise ValueError(
            "StructuredSpec: every quad_rows entry must also be listed in "
            f"dense_rows (quad_rows={spec.quad_rows.tolist()}, "
            f"dense_rows={spec.dense_rows.tolist()})")
    fi = structured_inequalities(spec, z)
    winv = -1.0 / fi  # 1/u, u = −fi > 0 at a strictly feasible iterate
    w2 = winv * winv

    # gradient: t·c_obj + Gᵀ(1/u); quadratic rows add 2(q⊙z)/u_row
    g = t * spec.c_obj + spec.C.T @ winv
    if spec.quad_rows.size:
        g = g + jnp.sum(
            (2.0 * winv[spec.quad_rows])[:, None] * spec.quad_diag, axis=0) * z

    # diagonal: single-nonzero rows + the quadratic rows' ∇²fi terms
    d = jnp.zeros_like(z)
    if spec.diag_rows.size:
        dr, dc = spec.diag_rows, spec.diag_cols
        d = d.at[dc].add(w2[dr] * spec.C[dr, dc] ** 2)
    if spec.quad_rows.size:
        d = d + jnp.sum(
            (2.0 * winv[spec.quad_rows])[:, None] * spec.quad_diag, axis=0)

    # pair rows: diagonal entries into d, off-diagonal couplings into h
    pr, px, pe = spec.pair_rows, spec.pair_x, spec.pair_elim
    if pr.size:
        a, b = spec.C[pr, px], spec.C[pr, pe]
        wp = w2[pr]
        d = d.at[px].add(wp * a * a).at[pe].add(wp * b * b)
        h = wp * a * b
    else:
        h = jnp.zeros((0,), z.dtype)

    # dense rows: Jacobian rows (with quadratic gradient corrections) → U
    Gd = spec.C[spec.dense_rows]
    for k, row in enumerate(spec.quad_rows):
        j = np.nonzero(spec.dense_rows == row)[0]
        if j.size:  # quadratic row that is also dense (the PCCP case)
            Gd = Gd.at[int(j[0])].add(2.0 * spec.quad_diag[k] * z)
    U = Gd.T  # (n, k_d)
    wd = w2[spec.dense_rows]
    return fi, g, d, h, U, wd


def structured_grad(spec: StructuredSpec, z: jnp.ndarray, t) -> jnp.ndarray:
    """Closed-form ∇φ (property-tested against ``jax.grad``)."""
    _, g, *_ = _structured_parts(spec, z, t)
    return g


def structured_hessian(spec: StructuredSpec, z: jnp.ndarray, t) -> jnp.ndarray:
    """Densely assembled ∇²φ from the structured parts (tests only —
    the solver never materializes this matrix)."""
    _, _, d, h, U, wd = _structured_parts(spec, z, t)
    H = jnp.diag(d) + (U * wd[None, :]) @ U.T
    if spec.pair_rows.size:
        px, pe = spec.pair_x, spec.pair_elim
        H = H.at[px, pe].add(h).at[pe, px].add(h)
    return H


def woodbury_solve(d: jnp.ndarray, U: jnp.ndarray, w: jnp.ndarray,
                   r: jnp.ndarray) -> jnp.ndarray:
    """Solve ``(diag(d) + U diag(w) Uᵀ) x = r`` by Sherman–Morrison–Woodbury.

    ``d`` (n,) must be strictly positive and ``w`` (k,) positive (an SPD
    diagonal + low-rank system — the regularized structured barrier
    Hessian after pair elimination). ``r`` is ``(n,)`` or ``(n, nrhs)``.
    The inner system is k×k — O(n·k) work instead of an O(n³) Cholesky.
    """
    rhs = r[:, None] if r.ndim == 1 else r
    dinv = 1.0 / d
    y0 = dinv[:, None] * rhs
    if U.shape[1]:
        M = jnp.diag(1.0 / w) + U.T @ (dinv[:, None] * U)  # SPD
        y = y0 - dinv[:, None] * (U @ jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(M), U.T @ y0))
    else:
        y = y0
    return y[:, 0] if r.ndim == 1 else y


def _structured_kkt_solve(spec: StructuredSpec, d, h, U, wd, g, reg_rel):
    """One Newton direction: solve H dz = −g on {a·dz = 0} via pair
    elimination + Woodbury, with scale-aware Tikhonov on the diagonal."""
    px, pe = spec.pair_x, spec.pair_elim
    diag_full = d + jnp.sum(U * U * wd[None, :], axis=-1)
    d = d + reg_rel * jnp.maximum(jnp.max(diag_full), 1.0)

    if pe.size:
        d_elim = d[pe]
        hdg = h / d_elim
        d_eff = d.at[px].add(-h * hdg)
    else:
        d_eff = d

    def solve(r):  # r: (n, nrhs); pair columns eliminated, then Woodbury
        if pe.size:
            r_core = r.at[px].add(-hdg[:, None] * r[pe]).at[pe].set(0.0)
        else:
            r_core = r
        y = woodbury_solve(d_eff, U, wd, r_core)
        if pe.size:
            y = y.at[pe].set((r[pe] - h[:, None] * y[px]) / d_elim[:, None])
        return y

    if spec.eq_vec is None:
        return solve(-g[:, None])[:, 0]
    sol = solve(jnp.stack([-g, spec.eq_vec], axis=1))
    v, wa = sol[:, 0], sol[:, 1]
    nu = jnp.dot(spec.eq_vec, v) / jnp.dot(spec.eq_vec, wa)
    return v - nu * wa


def _structured_newton_steps(spec: StructuredSpec, z, t, iters, reg_rel,
                             ls_iters, gate_tol):
    """Gated Newton loop on the structured barrier at parameter ``t``."""
    ss = jnp.asarray(0.5, z.dtype) ** jnp.arange(ls_iters, dtype=z.dtype)
    qr = spec.quad_rows

    def body(state):
        i, z, _ = state
        fi, g, d, h, U, wd = _structured_parts(spec, z, t)
        dz = _structured_kkt_solve(spec, d, h, U, wd, g, reg_rel)

        obj0 = jnp.dot(spec.c_obj, z)
        phi0 = t * obj0 - jnp.sum(jnp.log(-fi))
        slope = jnp.vdot(g, dz)
        # Newton decrement λ² = −g·dz bounds the remaining decrease of the
        # self-concordant barrier by λ²/2 — once that is noise relative to
        # φ, further steps cannot move the iterate.
        converged = -0.5 * slope <= gate_tol * (1.0 + jnp.abs(phi0))

        # Analytic batched line search: fi(z + s dz) is an O(m) update per
        # candidate from ONE precomputed matvec C dz — the quadratic rows
        # shift by s·lin + s²·qq in closed form. No re-assembly, no
        # re-matvec per candidate.
        Cdz = spec.C @ dz
        fi_s = fi[None, :] + ss[:, None] * Cdz[None, :]
        if qr.size:
            lin = 2.0 * jnp.sum(spec.quad_diag * (z * dz)[None, :], axis=-1)
            qq = jnp.sum(spec.quad_diag * (dz * dz)[None, :], axis=-1)
            fi_s = fi_s.at[:, qr].add(
                ss[:, None] * lin[None, :] + (ss * ss)[:, None] * qq[None, :])
        obj_s = t * (obj0 + ss * jnp.dot(spec.c_obj, dz))
        phi_s = obj_s - jnp.sum(jnp.log(-fi_s), axis=-1)
        ok = (
            jnp.all(fi_s < -1e-14, axis=-1)
            & jnp.isfinite(phi_s)
            & (phi_s <= phi0 + 0.25 * ss * slope)
        )
        found = jnp.any(ok)
        step = jnp.where(found, ss[jnp.argmax(ok)], jnp.asarray(0.0, z.dtype))
        z_new = jnp.where(converged | ~found, z, z + step * dz)
        # ~found leaves z unchanged, so iterating again would recompute the
        # exact same rejected step — stopping is equivalent and free.
        return i + 1, z_new, converged | ~found

    def cond(state):
        i, _, done = state
        return (i < iters) & ~done

    _, z, _ = jax.lax.while_loop(cond, body, (jnp.asarray(0), z, False))
    return z


def structured_barrier_solve(
    spec: StructuredSpec,
    z0: jnp.ndarray,
    t0: float = 1.0,
    mu: float = 12.0,
    outer_iters: int = 14,
    newton_iters: int = 18,
    reg_rel: float = _REG_REL,
    ls_iters: int = _LS_CANDIDATES,
    gate_tol: float = _GATE_TOL,
) -> BarrierResult:
    """Solve a :class:`StructuredSpec` from a strictly feasible ``z0``.

    Same barrier schedule semantics as :func:`barrier_solve`; every
    Newton step costs O(m·n) matvecs plus an O(n) KKT solve instead of an
    autodiff Hessian plus an O(n³) Cholesky.
    """
    z0 = jnp.asarray(z0, jnp.float64)
    m = spec.c0.shape[0]

    def stage(z, t):
        z = _structured_newton_steps(
            spec, z, t, newton_iters, reg_rel, ls_iters, gate_tol)
        return z, None

    ts = t0 * mu ** jnp.arange(outer_iters, dtype=jnp.float64)
    z, _ = jax.lax.scan(stage, z0, ts)
    fi = structured_inequalities(spec, z)
    objective = structured_objective(spec, z)
    return BarrierResult(
        z=z,
        objective=objective,
        max_violation=jnp.max(fi),
        duality_gap_bound=m / ts[-1],
        ok=jnp.all(jnp.isfinite(z)) & jnp.isfinite(objective),
    )


# ---------------------------------------------------------------------------
# Dense autodiff path (generic inequalities; A/B reference for the PCCP)
# ---------------------------------------------------------------------------


def _newton_steps(
    phi: Callable,
    ineq: Callable,
    A: Optional[jnp.ndarray],
    z: jnp.ndarray,
    iters: int,
    reg_rel: float,
    ls_iters: int = _LS_CANDIDATES,
    gate_tol: Optional[float] = None,
):
    n = z.shape[0]

    def step(z):
        g = jax.grad(phi)(z)
        H = jax.hessian(phi)(z)
        # Scale-aware Tikhonov: relative to max(diag H), so the dense and
        # structured paths stay conditioned identically across the PCCP
        # ρ-ramp (a fixed absolute reg is dominant early, inert late).
        H = H + (reg_rel * jnp.maximum(jnp.max(jnp.diag(H)), 1.0)) * jnp.eye(
            n, dtype=z.dtype)
        # H is SPD (barrier Hessian of a convex program + Tikhonov), so the
        # KKT system is solved by block elimination on one Cholesky factor:
        #   dz = v − W ν,  ν = (A W)⁻¹ A v,  H v = −g,  H W = Aᵀ.
        # One dpotrf on (n, n) replaces the (n+p)² LU — measurably faster
        # for the small batched systems the vmapped PCCP solves consist of.
        if A is not None:
            c = jax.scipy.linalg.cho_factor(H)
            vw = jax.scipy.linalg.cho_solve(
                c, jnp.concatenate([-g[:, None], A.T], axis=1))
            v, W = vw[:, 0], vw[:, 1:]
            nu = jax.scipy.linalg.cho_solve(  # A H⁻¹ Aᵀ is SPD
                jax.scipy.linalg.cho_factor(A @ W), A @ v)
            dz = v - W @ nu
        else:
            c = jax.scipy.linalg.cho_factor(H)
            dz = jax.scipy.linalg.cho_solve(c, -g)

        # Backtracking with explicit strict-feasibility + finiteness checks.
        # The classic loop halves s until the first acceptable step; with a
        # fixed trip count the candidates are independent, so we batch them
        # in ONE vmapped evaluation (same accepted step — the largest
        # acceptable s — but an ls_iters× shorter sequential dependency
        # chain inside the vmapped PCCP inner solves).
        phi0 = phi(z)
        slope = jnp.vdot(g, dz)
        ss = jnp.asarray(0.5, z.dtype) ** jnp.arange(ls_iters, dtype=z.dtype)

        def try_step(s):
            z_try = z + s * dz
            feas = jnp.all(ineq(z_try) < -1e-14)
            phi_try = phi(z_try)
            return feas & jnp.isfinite(phi_try) & (phi_try <= phi0 + 0.25 * s * slope)

        ok = jax.vmap(try_step)(ss)
        found = jnp.any(ok)
        step = jnp.where(found, ss[jnp.argmax(ok)], jnp.asarray(0.0, z.dtype))
        # If no feasible improving step exists we are at (numerical) optimum.
        return jnp.where(found, z + step * dz, z), phi0, slope, found

    if gate_tol is None:  # fixed-trip legacy path (bit-exact)
        def body(_, z):
            z_new, _, _, _ = step(z)
            return z_new

        return jax.lax.fori_loop(0, iters, body, z)

    def body(state):
        i, z, _ = state
        z_new, phi0, slope, found = step(z)
        converged = -0.5 * slope <= gate_tol * (1.0 + jnp.abs(phi0))
        return i + 1, jnp.where(converged, z, z_new), converged | ~found

    def cond(state):
        i, _, done = state
        return (i < iters) & ~done

    _, z, _ = jax.lax.while_loop(cond, body, (jnp.asarray(0), z, False))
    return z


def barrier_solve(
    spec: BarrierSpec,
    z0: jnp.ndarray,
    t0: float = 1.0,
    mu: float = 12.0,
    outer_iters: int = 14,
    newton_iters: int = 18,
    reg_rel: float = _REG_REL,
    ls_iters: int = _LS_CANDIDATES,
    gate_tol: Optional[float] = None,
) -> BarrierResult:
    """Solve ``spec`` starting from a strictly feasible ``z0``.

    With the defaults the final barrier parameter is t0 * mu**13 ≈ 1e14, so
    the suboptimality bound m/t is far below solver noise for our m ≈ 30.

    ``gate_tol`` (None = fixed trip counts, the bit-exact legacy
    behaviour) enables the Newton-decrement early exit per barrier stage.
    """
    z0 = jnp.asarray(z0, jnp.float64)
    m = spec.inequalities(z0).shape[0]
    A = spec.eq_matrix

    def stage(carry, t):
        z = carry

        def phi(zz):
            fi = spec.inequalities(zz)
            return t * spec.objective(zz) - jnp.sum(jnp.log(-fi))

        z = _newton_steps(phi, spec.inequalities, A, z, newton_iters, reg_rel,
                          ls_iters, gate_tol)
        return z, None

    ts = t0 * mu ** jnp.arange(outer_iters, dtype=jnp.float64)
    z, _ = jax.lax.scan(stage, z0, ts)
    fi = spec.inequalities(z)
    objective = spec.objective(z)
    return BarrierResult(
        z=z,
        objective=objective,
        max_violation=jnp.max(fi),
        duality_gap_bound=m / ts[-1],
        ok=jnp.all(jnp.isfinite(z)) & jnp.isfinite(objective),
    )
