import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combo.

The two lines above MUST stay first: jax locks the device count on first
initialization. Everything below is ordinary imports.

Per combo this produces: compile success, per-device memory analysis,
HLO FLOPs/bytes (cost_analysis), and per-type collective bytes parsed from
the partitioned HLO — the §Roofline inputs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k [--multi-pod] [--out results/x.json]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax  # noqa: E402

from repro.configs.base import INPUT_SHAPES  # noqa: E402
from repro.configs.registry import ARCH_IDS, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import steps as S  # noqa: E402
from repro.parallel import sharding as shd  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\])\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device collective bytes by op type from partitioned HLO.

    all-reduce is weighted 2× (ring: reduce-scatter + all-gather phases);
    others count their (already per-device) output buffer once.
    """
    out: Dict[str, float] = {}
    for type_str, op in _COLL_RE.findall(hlo_text):
        nbytes = _type_bytes(type_str)
        if op == "all-reduce":
            nbytes *= 2
        out[op] = out.get(op, 0.0) + float(nbytes)
    return out


def should_skip(arch: str, shape_name: str) -> Optional[str]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if cfg.encoder_decoder and shape.name == "long_500k":
        return ("enc-dec full-attention decoder has no 500k-decode analogue "
                "(DESIGN.md §5) — skipped")
    return None


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            verbose: bool = True, param_fsdp: bool = True,
            param_mode: str = None, microbatches: int = 1) -> Dict:
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "param_fsdp": param_fsdp, "param_mode": param_mode,
                 "microbatches": microbatches}
    skip = should_skip(arch, shape_name)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    # Activation constraints stay ON in every mode: without them GSPMD
    # drops the batch sharding at scan boundaries and replicates compute
    # (§Perf iteration C4, refuted — 16x flops, 2.4 TB all-reduce).
    shd.set_activation_mesh(mesh)
    n_dev = mesh.size
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step, opt = S.build_train_step(cfg, microbatches=microbatches)
            params, opt_state = S.abstract_state(cfg, opt)
            batch = S.batch_specs(cfg, shape)
            p_sh = shd.param_shardings(params, mesh)
            # opt-state shardings mirror params; the step scalar is replicated
            from repro.train.optimizer import AdamWState
            o_sh = AdamWState(
                step=shd.replicated(mesh),
                mu=shd.param_shardings(params, mesh),
                nu=shd.param_shardings(params, mesh),
            )
            b_sh = shd.batch_shardings(mesh, batch)
            lowered = jax.jit(
                step, in_shardings=(p_sh, o_sh, b_sh), donate_argnums=(0, 1)
            ).lower(params, opt_state, batch)
        elif shape.kind == "prefill":
            step = S.build_prefill_step(cfg)
            params = S.abstract_state(cfg, S.build_train_step(cfg)[1])[0]
            batch = S.batch_specs(cfg, shape)
            p_sh = shd.param_shardings(params, mesh, fsdp=param_fsdp, mode=param_mode)
            b_sh = shd.batch_shardings(mesh, batch)
            lowered = jax.jit(step, in_shardings=(p_sh, b_sh)).lower(params, batch)
        else:  # decode
            step = S.build_decode_step(cfg)
            params = S.abstract_state(cfg, S.build_train_step(cfg)[1])[0]
            tokens, cache, pos = S.decode_specs(cfg, shape)
            p_sh = shd.param_shardings(params, mesh, fsdp=param_fsdp, mode=param_mode)
            t_sh = shd.batch_sharding(mesh, tokens.shape[0], 2)
            c_sh = shd.cache_shardings(mesh, cache)
            lowered = jax.jit(
                step, in_shardings=(p_sh, t_sh, c_sh, shd.replicated(mesh)),
                donate_argnums=(2,),
            ).lower(params, tokens, cache, pos)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    from repro.launch.hlo_analysis import analyze

    hlo = analyze(compiled.as_text())
    coll = hlo.collective_bytes
    rec.update(
        hlo_loop_aware_flops_per_dev=hlo.flops,
        hlo_loop_aware_dot_bytes_per_dev=hlo.dot_bytes,
        hlo_while_trip_counts=hlo.trip_counts,
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        num_devices=n_dev,
        arg_bytes_per_dev=getattr(ma, "argument_size_in_bytes", None),
        temp_bytes_per_dev=getattr(ma, "temp_size_in_bytes", None),
        out_bytes_per_dev=getattr(ma, "output_size_in_bytes", None),
        alias_bytes_per_dev=getattr(ma, "alias_size_in_bytes", None),
        hlo_flops_per_dev=float(ca.get("flops", -1.0)),
        hlo_bytes_per_dev=float(ca.get("bytes accessed", -1.0)),
        collective_bytes_per_dev=coll,
        model_flops_total=S.model_flops_estimate(cfg, shape),
    )
    if verbose:
        hbm = (rec["arg_bytes_per_dev"] + rec["temp_bytes_per_dev"]
               + rec["out_bytes_per_dev"] - rec["alias_bytes_per_dev"]) / 2**30
        print(f"[{arch} × {shape_name} × {mesh_name}] compile {t_compile:.1f}s "
              f"~{hbm:.2f} GiB/dev, {hlo.flops/1e12:.3f} TFLOP/dev (loop-aware), "
              f"coll={ {k: round(v/2**20, 1) for k, v in coll.items()} } MiB")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-param-fsdp", action="store_true",
                    help="serve-mode weights: model/expert sharding only")
    ap.add_argument("--param-mode", default=None,
                    choices=("fsdp", "resident", "replicated"))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    rec = run_one(args.arch, args.shape, args.multi_pod,
                  param_fsdp=not args.no_param_fsdp,
                  param_mode=args.param_mode,
                  microbatches=args.microbatches)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=2)
    print(json.dumps({k: v for k, v in rec.items() if k != "reason"}, default=str))


if __name__ == "__main__":
    main()
