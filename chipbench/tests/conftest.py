import jax
import pytest


@pytest.fixture(autouse=True, scope="package")
def benchmark_jax_config():
    """The benchmark draws its fleets in float64 with JAX's original
    threefry layout (``chipbench.run.configure_jax`` sets both for a run)."""
    before = (jax.config.jax_threefry_partitionable, jax.config.jax_enable_x64)
    jax.config.update("jax_threefry_partitionable", False)
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_threefry_partitionable", before[0])
    jax.config.update("jax_enable_x64", before[1])
