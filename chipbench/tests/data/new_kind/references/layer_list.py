"""Plain reference of the ``layer_list`` kind: its deployment's columns
worked out from the layers in numpy, planned by Algorithm 2 of
``chipbench.reference``, whose constraint this kind keeps."""
import numpy as np

from chipbench.reference import Deployment, constraint_excess, plan  # noqa: F401

FIELDS = ("d", "w", "g", "v", "t_vm")


def _columns(gr: dict, dt):
    """(d bits, w FLOPs, g, v s², t_vm s) at each split point 0..L."""
    ly = gr["layers"]
    col = lambda k: np.asarray(ly[k], dt)
    later = lambda x: np.concatenate([np.cumsum(x[::-1])[::-1], [0.0]])
    d = np.asarray([gr["input_mb"], *ly["out_mb"]], dt) * dt(8e6)
    w = np.concatenate([[0.0], np.cumsum(col("gflops"))]).astype(dt) * dt(1e9)
    g = np.asarray([1.0, *ly["g_eff"]], dt)
    v_loc = np.concatenate([[0.0], np.cumsum(col("var_ms2"))]).astype(dt)
    v_vm = later(col("edge_var_ms2")).astype(dt)
    t_vm = later(col("edge_ms")).astype(dt) * dt(1e-3)
    return d, w, g, v_loc * dt(1e-6) + v_vm * dt(1e-6), t_vm


def deployment(config: dict, gains, dtype=np.float64) -> Deployment:
    """The fleet of ``config`` for link ``gains`` ((R, N)), devices in
    group order, chains padded to the widest with invalid points."""
    groups = config["groups"]
    cols = [_columns(gr, dtype) for gr in groups]
    width = max(len(c[0]) for c in cols)
    rows = {k: [] for k in Deployment._fields if k != "snr_b"}
    for gr, c in zip(groups, cols, strict=True):
        n, k, pl = int(gr["count"]), len(c[0]), gr["platform"]
        for name, a in zip(FIELDS, c, strict=True):
            padded = np.concatenate([a, np.repeat(a[-1:], width - k)])
            rows[name].append(np.tile(padded, (n, 1)))
        rows["valid"].append(np.tile(np.arange(width) < k, (n, 1)))
        for name, key in (("kappa", "kappa"), ("f_min", "f_min_hz"),
                          ("f_max", "f_max_hz"), ("p_tx", "p_tx_w")):
            rows[name].append(np.full(n, pl[key], dtype))
    dep = {k: np.concatenate(v) for k, v in rows.items()}
    n0 = dtype(10.0 ** ((config["channel"]["noise_dbm_per_hz"] - 30.0) / 10.0))
    gains = np.atleast_2d(np.asarray(gains, np.float64)).astype(dtype)
    return Deployment(snr_b=dep["p_tx"] * gains / n0, **dep)
