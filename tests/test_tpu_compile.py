"""The planner's float64 solves compile for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described topology.
It refuses what the chip cannot run — an LU decomposition in float64,
for one — so these tests guard the solves of the PCCP inner problem at
the shape the paper's AlexNet fleet (N=12, five multi-start lanes) gives
them. The topology is described inside a fixture, never at import, and
every test skips where it cannot be described.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import pccp
from repro.solvers.ipm import woodbury_solve

N_DEVICES, STARTS, M1 = 12, 5, 9  # AlexNet: 9 partition points per device
DIM = 2 * M1 + 4  # PCCP inner variables z = [x, y, α, β, δ, γ]
RANK = 3  # Woodbury rank: the deadline row and the two DC rows


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles are written to the persistent cache but cannot be read back
    # without a chip; keep them out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_woodbury_solve_compiles_at_f64(one_chip):
    batch = (STARTS, N_DEVICES)
    solve = jax.vmap(jax.vmap(woodbury_solve))
    _compile(solve,
             _spec(batch + (DIM,), jnp.float64, one_chip),
             _spec(batch + (DIM, RANK), jnp.float64, one_chip),
             _spec(batch + (RANK,), jnp.float64, one_chip),
             _spec(batch + (DIM, 2), jnp.float64, one_chip))


def _inner(solver):
    """The PCCP inner problem over the fleet, as the planner vmaps it."""
    def fn(e, t, var, sigma, deadline, x_prev, y_prev):
        return jax.vmap(
            lambda *a: pccp._inner_problem(*a[:5], 10.0, *a[5:],
                                           solver=solver))(
            e, t, var, sigma, deadline, x_prev, y_prev)
    return fn


@pytest.mark.parametrize("solver", ["structured", "dense"])
def test_pccp_inner_barrier_compiles_at_f64(one_chip, solver):
    """``structured`` runs ``structured_barrier_solve`` (Woodbury KKT);
    ``dense`` runs the autodiff ``barrier_solve`` (Cholesky KKT)."""
    tab = _spec((N_DEVICES, M1), jnp.float64, one_chip)
    vec = _spec((N_DEVICES,), jnp.float64, one_chip)
    _compile(_inner(solver), tab, tab, tab, vec, vec, tab, vec)

