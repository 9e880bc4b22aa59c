"""The ``layer_list`` kind, the program's side: a chain stated layer by
layer (FLOPs, output size, the prefix's FLOPs per cycle, local variance,
edge time and its variance per layer), its split points' columns summed
from the layers, the edge's time and variance those of the layers left
after the split."""
import numpy as np


def _after(x):
    """Per split point 0..L, the sum over the layers after it."""
    return np.concatenate([np.cumsum(x[::-1])[::-1], [0.0]])


def device_specs(config: dict) -> tuple:
    import jax.numpy as jnp

    from repro.core import DeviceSpec
    from repro.core.blocks import BlockChain

    out = []
    for gr in config["groups"]:
        ly, pl = gr["layers"], gr["platform"]
        col = lambda k: np.asarray(ly[k], np.float64)
        cols = (np.asarray([gr["input_mb"], *ly["out_mb"]]) * 8e6,
                np.concatenate([[0.0], np.cumsum(col("gflops"))]) * 1e9,
                np.asarray([1.0, *ly["g_eff"]]),
                np.concatenate([[0.0], np.cumsum(col("var_ms2"))]) * 1e-6,
                _after(col("edge_ms")) * 1e-3,
                _after(col("edge_var_ms2")) * 1e-6)
        out.append(DeviceSpec(
            chain=BlockChain(*(jnp.asarray(c, jnp.float64) for c in cols)),
            kappa=pl["kappa"], f_min_hz=pl["f_min_hz"],
            f_max_hz=pl["f_max_hz"], p_tx_w=pl["p_tx_w"],
            count=int(gr["count"]), name=gr["name"]))
    return tuple(out)
