"""Rehearse ``chip_smoke.py`` without a chip.

    JAX_PLATFORMS=cpu python chip_rehearsal.py            # one chip
    JAX_PLATFORMS=cpu python chip_rehearsal.py --chips 4  # four chips

Runs the phases of ``chip_smoke.py`` here on the CPU at their real sizes
(with ``--chips 4`` on four virtual CPU devices), recording every
top-level call of a jitted program of ``repro`` with the shapes it was
given. It then compiles each recorded program for a described TPU v5e
(one chip, or the 2x2 mesh) and prints, per program, the compile seconds
and ``memory_analysis()``. A program the chip's compiler refuses fails
the run. Nothing here is a chip measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
#: TPU compiles at once: each float64 planner compile holds GiBs of host
#: memory, so more than two can exhaust a 64 GiB host
COMPILE_WORKERS = 2


class Recorder:
    """Wraps the jitted programs of ``repro`` and records top-level calls
    (calls made while tracing an outer program belong to that program)."""

    def __init__(self):
        self.calls = {}  # key -> (label, rebuild, args, kwargs)
        self._lock = threading.Lock()

    def _record(self, label, rebuild, args, kwargs):
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return
        def abstract(x):  # a traced Python number does not key a compile
            if hasattr(x, "shape"):
                return x.shape, str(x.dtype)
            return type(x).__name__ if type(x) in (int, float) else x

        sig = (jax.tree_util.tree_map(abstract, args), kwargs)
        key = (label, repr(sig))
        with self._lock:
            self.calls.setdefault(key, (label, rebuild, args, kwargs))

    def wrap(self, label, fn, rebuild):
        def wrapped(*args, **kwargs):
            self._record(label, rebuild, args, kwargs)
            return fn(*args, **kwargs)

        wrapped.__wrapped__ = fn
        for attr in ("_cache_size", "lower", "trace", "eval_shape"):
            if hasattr(fn, attr):
                setattr(wrapped, attr, getattr(fn, attr))
        return wrapped

    def install(self):
        """Patch every module-level jitted function of ``repro`` (in every
        module that holds it) and the per-mesh program factories of
        ``core.decompose``."""
        import importlib
        import pathlib

        from repro.core import decompose

        src = pathlib.Path(ROOT, "src")
        mods = []
        for path in sorted(src.glob("repro/**/*.py")):
            name = ".".join(path.relative_to(src).with_suffix("").parts)
            name = name.removesuffix(".__init__")
            if name.split(".")[1] in ("core", "solvers", "serve",
                                      "parallel", "configs"):
                mods.append(importlib.import_module(name))
        jitted = {}
        for m in mods:
            for name, obj in list(vars(m).items()):
                if hasattr(obj, "lower") and hasattr(obj, "_cache_size"):
                    jitted.setdefault(id(obj), (f"{m.__name__}.{name}", obj))
        for label, obj in jitted.values():
            wrapped = self.wrap(label, obj, lambda mesh, obj=obj: obj)
            for m in mods:
                for name, val in list(vars(m).items()):
                    if val is obj:
                        setattr(m, name, wrapped)
        for fname in ("_group_programs", "_optimal_programs"):
            factory = getattr(decompose, fname)
            setattr(decompose, fname, self._wrap_factory(fname, factory))

    def _wrap_factory(self, fname, factory):
        def wrapped(mesh, *statics):
            progs = factory(mesh, *statics)
            fields = {}
            for field in progs._fields:
                rebuild = (lambda m, field=field:
                           getattr(factory(m, *statics), field))
                fields[field] = self.wrap(
                    f"decompose.{fname}.{field}", getattr(progs, field),
                    rebuild)
            return type(progs)(**fields)

        return wrapped


def _abstract(tree, one, mesh):
    """Array leaves → shape structs on the described chip(s): mesh-sharded
    leaves keep their PartitionSpec on the described mesh."""
    import jax
    from jax.sharding import NamedSharding

    def conv(x):
        if not hasattr(x, "shape") or not hasattr(x, "dtype"):
            return x
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding) and mesh is not None:
            target = NamedSharding(mesh, sh.spec)
        elif mesh is not None and mesh.devices.size > 1:
            target = NamedSharding(mesh, jax.sharding.PartitionSpec())
        else:
            target = one
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=target)

    return jax.tree_util.tree_map(conv, tree)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if args.chips == 4:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)

    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding

    import chip_smoke
    from chipbench.run import CompileClock

    if jax.devices()[0].platform != "cpu":
        print("chip_rehearsal: run with JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    chip_smoke._setup()
    # TPU compiles land in the persistent cache but cannot be read back
    # without a chip; keep them out of it
    jax.config.update("jax_enable_compilation_cache", False)
    rec = Recorder()
    rec.install()
    clock = CompileClock()

    t0 = time.perf_counter()
    devices = jax.devices()
    if args.chips == 4:
        chip_smoke.run_four_chips(devices[:4], clock)
    else:
        chip_smoke.run_one_chip(devices[0], clock)
    print(json.dumps({"cpu_run_s": time.perf_counter() - t0,
                      "programs": len(rec.calls)}), flush=True)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    mesh = Mesh(topo.devices[:args.chips], ("devices",))

    def compile_one(item):
        label, rebuild, a, kw = item
        fn = rebuild(mesh)
        t = time.perf_counter()
        try:
            c = fn.lower(*_abstract(a, one, mesh),
                         **_abstract(kw, one, mesh)).compile()
        except Exception as e:  # the chip's compiler refused it
            return label, time.perf_counter() - t, None, e
        return label, time.perf_counter() - t, c.memory_analysis(), None

    failed = 0
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=COMPILE_WORKERS) as pool:
        for label, secs, mem, err in pool.map(compile_one,
                                              rec.calls.values()):
            row = {"program": label, "compile_s": secs}
            if err is not None:
                failed += 1
                row["error"] = f"{type(err).__name__}: {str(err)[:400]}"
            else:
                row.update(temp_bytes=mem.temp_size_in_bytes,
                           argument_bytes=mem.argument_size_in_bytes,
                           output_bytes=mem.output_size_in_bytes,
                           code_bytes=mem.generated_code_size_in_bytes)
            print(json.dumps(row), flush=True)
    print(json.dumps({"v5e_compile_wall_s": time.perf_counter() - t0,
                      "failed": failed}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
