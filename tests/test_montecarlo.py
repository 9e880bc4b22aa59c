"""Moment matching of ``montecarlo._sample_matched`` (satellite task).

The planner's guarantee is distribution-free given (mean, variance), so
the Monte-Carlo validator must actually *hit* the requested moments for
every family it claims to sample. Gamma and lognormal match exactly by
construction; truncnorm is **approximate** — it clips a moment-matched
normal at zero, which biases the mean up and shrinks the variance, with
the bias growing with the coefficient of variation (documented here: at
cv ≤ 0.8 the relative mean bias is ≤ ~4%, E[max(X,0)] − μ =
σφ(μ/σ) − μΦ(−μ/σ) ≥ 0).

Property tests (hypothesis, via the ``_hyp`` shim) sweep (mean, cv)
with a *fixed* PRNG key, so every example is deterministic; plain
parametrized tests keep coverage when hypothesis is absent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core.montecarlo import _sample_matched

N_SAMPLES = 200_000
KEY = jax.random.PRNGKey(42)

MEANS = st.floats(min_value=1e-3, max_value=5.0)
CVS = st.floats(min_value=0.05, max_value=0.8)


def _draw(dist, mean, cv):
    var = (cv * mean) ** 2
    x = _sample_matched(KEY, dist, jnp.float64(mean), jnp.float64(var),
                        (N_SAMPLES,))
    return np.asarray(x), var


@pytest.mark.parametrize("dist", ["gamma", "lognormal"])
@given(mean=MEANS, cv=CVS)
@settings(max_examples=10, deadline=None)
def test_exact_families_match_both_moments(dist, mean, cv):
    x, var = _draw(dist, mean, cv)
    assert np.isfinite(x).all() and (x >= 0.0).all()
    np.testing.assert_allclose(x.mean(), mean, rtol=0.02)
    np.testing.assert_allclose(x.var(), var, rtol=0.12)


@given(mean=MEANS, cv=CVS)
@settings(max_examples=10, deadline=None)
def test_truncnorm_matches_approximately_with_positive_mean_bias(mean, cv):
    x, var = _draw("truncnorm", mean, cv)
    assert (x >= 0.0).all()
    sigma = np.sqrt(var)
    alpha = mean / sigma
    # analytic clipping bias of max(N(mean, var), 0)
    from math import erf, exp, pi, sqrt

    phi = exp(-0.5 * alpha**2) / sqrt(2 * pi)
    Phi_neg = 0.5 * (1.0 - erf(alpha / sqrt(2.0)))
    bias = sigma * phi - mean * Phi_neg
    assert bias >= 0.0
    se = sigma / np.sqrt(N_SAMPLES)
    assert abs(x.mean() - (mean + bias)) <= 6.0 * se  # matches *clipped* moments
    assert x.mean() >= mean - 6.0 * se  # bias never pulls the mean down
    assert abs(x.mean() - mean) <= 0.05 * mean + 6.0 * se  # ≤ ~4% at cv ≤ 0.8
    assert x.var() <= var * 1.05  # clipping only shrinks the variance


@pytest.mark.parametrize("dist", ["gamma", "lognormal", "truncnorm"])
def test_fixed_case_moments(dist):
    """Hypothesis-free smoke pin: one representative (mean, cv) per family."""
    x, var = _draw(dist, 0.15, 0.3)
    rtol_mean = 0.03 if dist == "truncnorm" else 0.01
    np.testing.assert_allclose(x.mean(), 0.15, rtol=rtol_mean)
    np.testing.assert_allclose(x.var(), var, rtol=0.15)


def test_unknown_dist_raises():
    with pytest.raises(ValueError, match="unknown dist"):
        _sample_matched(KEY, "cauchy", 1.0, 1.0, (8,))


@pytest.mark.parametrize("dist", ["gamma", "lognormal", "truncnorm",
                                  "pareto", "weibull"])
def test_paired_sampling_bit_identical_to_separate_calls(dist):
    """``sample_local_and_vm`` draws both tiers in one vmapped sampler
    call; each tier must get exactly the samples its own
    ``_sample_matched`` call draws (the validators' goldens are pinned
    bit for bit)."""
    from repro.core.montecarlo import sample_local_and_vm

    k_loc, k_vm = jax.random.split(KEY)
    m_loc, m_vm = jnp.array([0.02, 0.05, 1e-3]), jnp.array([0.01, 0.2, 0.03])
    v_loc, v_vm = (0.3 * m_loc) ** 2, (0.6 * m_vm) ** 2
    shape = (500, 3)
    s_loc, s_vm = sample_local_and_vm(k_loc, k_vm, dist, m_loc, v_loc,
                                      m_vm, v_vm, shape)
    np.testing.assert_array_equal(
        np.asarray(s_loc),
        np.asarray(_sample_matched(k_loc, dist, m_loc, v_loc, shape)))
    np.testing.assert_array_equal(
        np.asarray(s_vm),
        np.asarray(_sample_matched(k_vm, dist, m_vm, v_vm, shape)))
