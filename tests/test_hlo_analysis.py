"""Loop-aware HLO analyzer: verify against a known scanned program."""
import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze, split_computations


def _scanned_matmul(n_layers: int, d: int):
    def step(x, w):
        return jnp.tanh(x @ w), None

    def fn(x, ws):
        y, _ = jax.lax.scan(step, x, ws)
        return y.sum()

    x = jax.ShapeDtypeStruct((8, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((n_layers, d, d), jnp.float32)
    return jax.jit(fn).lower(x, ws).compile()


def test_trip_count_and_flops():
    L, D = 7, 64
    compiled = _scanned_matmul(L, D)
    cost = analyze(compiled.as_text())
    assert L in cost.trip_counts
    expected = 2 * 8 * D * D * L  # 2·M·K·N per layer × L layers
    assert 0.9 * expected <= cost.flops <= 1.6 * expected, (cost.flops, expected)
    # XLA's own cost analysis undercounts the loop body (the reason this
    # module exists): it must be ≈ L× below ours.
    xla = compiled.cost_analysis()["flops"]
    assert cost.flops > 2.0 * xla


def test_split_computations_finds_entry():
    compiled = _scanned_matmul(3, 16)
    comps = split_computations(compiled.as_text())
    assert "__entry__" in comps
    assert len(comps) >= 3  # entry + cond + body at least


def test_no_collectives_single_device():
    compiled = _scanned_matmul(3, 16)
    cost = analyze(compiled.as_text())
    assert cost.collective_bytes == {}
