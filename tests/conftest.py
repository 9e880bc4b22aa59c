import jax
import pytest

# The goldens (tests/golden/*.json) and the replay incident were drawn
# with JAX's original threefry bit layout; newer JAX defaults to the
# partitionable layout, which draws different fleets from the same key.
# Pin the layout the test data was made with.
jax.config.update("jax_threefry_partitionable", False)

# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device
# (the 512-device override lives only in launch/dryrun.py).

# Property-based tests import hypothesis through tests/_hyp.py, which
# degrades to per-test skips when hypothesis is absent (bare jax-only
# env) — plain tests in the same modules still collect and run.


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)
