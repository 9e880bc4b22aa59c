"""Link gains of a deployment's devices, drawn from the run's seed.

The recipe of the paper's §VI-A: devices uniform in a square with the
edge node at its centre, the distance floored, and the 3GPP TR 36.931
pico-cell path loss PL(dB) = 38 + 30 log10(r / 1 m). Request ``i`` of a
run draws from its own key (``request_key``), so the same seed gives the
same fleets, and seed 0's request 0 places its devices with
``PRNGKey(0)``, the key of the repository's golden plans (the gains
agree with the program's op-by-op draw to a few ulp). The draw uses
JAX's original threefry layout (``jax_threefry_partitionable=False``)
in float64, which the caller sets before any key is made.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def request_key(seed, i):
    """The key of request ``i`` (≥ 0) of a run with ``seed``: the seed's
    own key for request 0, the key folded with ``i`` after it."""
    key = jax.random.PRNGKey(seed)
    return jnp.where(i == 0, key, jax.random.fold_in(key, i))


@partial(jax.jit, static_argnames=("n", "area_m", "min_dist_m", "pl_1m_db",
                                   "pl_decade_db"))
def draw_gains(seed, i, *, n, area_m, min_dist_m, pl_1m_db, pl_decade_db):
    """(n,) float64 linear link gains of request ``i``'s devices, drawn
    from their positions in one program."""
    xy = jax.random.uniform(request_key(seed, i), (n, 2), jnp.float64,
                            -area_m / 2, area_m / 2)
    r = jnp.maximum(jnp.linalg.norm(xy, axis=-1), min_dist_m)
    pl_db = pl_1m_db + pl_decade_db * jnp.log10(r)
    return 10.0 ** (-pl_db / 10.0)


def gains_for(config: dict, seed: int, i: int, device=None):
    """Request ``i``'s gains of ``config``'s whole fleet (group order)."""
    if not jax.config.jax_enable_x64:
        raise RuntimeError("the fleet draw needs jax_enable_x64")
    ch = config["channel"]
    seed = jax.device_put(np.int64(seed), device)
    i = jax.device_put(np.int32(i), device)
    return draw_gains(seed, i,
                      n=sum(int(g["count"]) for g in config["groups"]),
                      area_m=float(ch["area_m"]),
                      min_dist_m=float(ch["min_dist_m"]),
                      pl_1m_db=float(ch["pathloss_db_at_1m"]),
                      pl_decade_db=float(ch["pathloss_db_per_decade"]))
