"""Solver-layer unit tests (bisection, golden, LM, barrier IPM)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.solvers import BarrierSpec, barrier_solve, bisect, golden_section
from repro.solvers.nls import fit_inverse_frequency, levenberg_marquardt


def test_bisect_root():
    r = bisect(lambda x: x * x - 2.0, 0.0, 2.0)
    assert abs(float(r) - np.sqrt(2)) < 1e-10


@settings(max_examples=20, deadline=None)
@given(st.floats(-3.0, 3.0))
def test_golden_quadratic(c):
    g = golden_section(lambda x: (x - c) ** 2, -5.0, 5.0)
    assert abs(float(g) - c) < 1e-6


@pytest.mark.parametrize("fn, lo, hi, x_min", [
    # asymmetric convex: exp on the right, linear on the left of the min
    (lambda x: jnp.expm1(x - 1.3) - (x - 1.3), -5.0, 5.0, 1.3),
    # rising on the whole bracket: the min is the floor (b_lo)
    (lambda x: 1.0 / x + 10.0 * x, 1.0, 5.0, 1.0),
    # falling on the whole bracket: the min is the top (B at λ = 0)
    (lambda x: 1.0 / x, 0.5, 4.0, 4.0),
], ids=["asymmetric", "at_lo", "at_hi"])
def test_golden_one_evaluation_per_step(fn, lo, hi, x_min):
    iters = 72
    calls = []

    def counted(x):
        calls.append(x)
        return fn(x)

    # (a) iters + 2 evaluations: two for the first interior points, one
    # per shrink (``eval_shape``'s abstract call of ``fn`` is no evaluation)
    with jax.disable_jit():
        g = golden_section(counted, lo, hi, iters)
    assert sum(not isinstance(x, jax.core.Tracer) for x in calls) == iters + 2
    # (c) the argmin to 1e-9 of the bracket, eager and compiled
    g_jit = jax.jit(golden_section, static_argnums=(0, 3))(fn, lo, hi, iters)
    for x in (g, g_jit):
        assert abs(float(x) - x_min) <= 1e-9 * (hi - lo)
    # (b) the traced program applies ``fn`` once, at width 1
    jaxpr = jax.make_jaxpr(
        lambda a, b: golden_section(lambda x: jnp.cosh(fn(x)), a, b, iters)
    )(lo, hi)
    coshes = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cosh"]
    assert len(coshes) == 1
    assert coshes[0].invars[0].aval.shape == ()


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested jaxprs (loop bodies) included."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_lm_fits_inverse_frequency():
    f = jnp.linspace(0.1e9, 1.2e9, 15)
    t = 0.35e9 / f
    res = fit_inverse_frequency(f, t)
    assert abs(float(res.params[0]) - 0.35e9) / 0.35e9 < 1e-6
    assert float(res.residual_norm_sq) < 1e-12


def test_lm_rosenbrock_converges():
    def resid(x):
        return jnp.array([10.0 * (x[1] - x[0] ** 2), 1.0 - x[0]])

    out = levenberg_marquardt(resid, jnp.array([-1.2, 1.0]), iters=200)
    assert np.allclose(np.asarray(out.params), [1.0, 1.0], atol=1e-6)


def test_ipm_matches_scipy():
    scipy = pytest.importorskip("scipy.optimize")
    # min x1^2 + 2 x2^2 + x1 x2  s.t. x1 + x2 = 1, x1 >= 0.1, x2 >= 0.1
    Q = np.array([[2.0, 1.0], [1.0, 4.0]])

    def f(x):
        return 0.5 * x @ Q @ x

    res = scipy.minimize(f, [0.5, 0.5], constraints=[{"type": "eq", "fun": lambda x: x.sum() - 1}],
                         bounds=[(0.1, None), (0.1, None)])
    spec = BarrierSpec(
        objective=lambda z: 0.5 * z @ jnp.asarray(Q) @ z,
        inequalities=lambda z: jnp.array([0.1 - z[0], 0.1 - z[1]]),
        eq_matrix=jnp.array([[1.0, 1.0]]),
        eq_rhs=jnp.array([1.0]),
    )
    out = barrier_solve(spec, jnp.array([0.5, 0.5]))
    assert np.allclose(np.asarray(out.z), res.x, atol=1e-6)
    assert float(out.max_violation) <= 1e-9


def test_ipm_active_inequality():
    spec = BarrierSpec(
        objective=lambda z: (z[0] + 2.0) ** 2,
        inequalities=lambda z: jnp.array([1.0 - z[0], z[0] - 50.0]),
    )
    out = barrier_solve(spec, jnp.array([5.0]))
    assert abs(float(out.z[0]) - 1.0) < 1e-6
