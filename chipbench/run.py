"""Benchmark of the planner on a TPU: one run of one cell.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout. The cell (an entry of ``workloads`` in
``BENCHMARK.json``) names a deployment, ``chipbench/configs/<config>.json``,
and a traffic mix, ``chipbench/traffic/<traffic>.json``. The run

1. exits with code 2, before any work, where JAX finds no TPU or fewer
   chips than the cell asks for;
2. sets up: imports, builds the planner and the fleet spec, and makes one
   warm-up request at the cell's shapes (compiles, or loads the programs
   from JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR``, else
   ``.jax_cache/`` in the checkout); ``setup_s`` is the time from the
   process's start to the first timed request;
3. runs requests closed loop, one client, for ``--seconds``; request i
   draws a fresh fleet from (seed, i), plans it and fetches the plan;
   ``plan_s`` is the seconds from the window's start to the end of the
   last request begun inside it, over the requests completed;
4. with ``--trace 1``, profiles the first ``trace_requests`` requests of
   the window (of the traffic file) and reduces the trace to the cell's
   per-layer metrics (``chipbench/metrics/<metric>.py``);
5. compares a sample of the window's plans, drawn from the seed, with the
   plain reference (``chipbench.check``).

A cell runs on the first ``chips`` devices that JAX lists, the planner's
lanes split across all of them. Per-chip readings are of the fullest
chip (``memory_peak_bytes``), of the busiest device plane of the trace
(``launches_per_plan``, ``plan_program_ms``), or averaged over the
planes (``busy_s``, ``idle_share.plan``, the ``breakdown``).

Earlier lines of standard output carry the set-up split, the compile
count of the window and each request's seconds (draw, plan, fetch) with
the process's CPU seconds, page faults, context switches and garbage
collection over it, the machine's idle and stolen core seconds, and the
seconds of the comparison with the host's peak resident memory; the
last line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``), then ``check``, each compared number
beside its limit. The same numbers close standard error.
"""
from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NO_CHIP = 2


def _since_process_start() -> float:
    """Seconds since this process started (from /proc), or since this
    module was imported where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


# ---------------------------------------------------------------------------
# lookup by name: every config, traffic mix and per-layer metric is a file
# ---------------------------------------------------------------------------

def _lookup(kind: str, name: str, ext: str) -> str:
    path = os.path.join(HERE, kind, name + ext)
    if not os.path.isfile(path):
        known = sorted(f[: -len(ext)] for f in os.listdir(os.path.join(HERE, kind))
                       if f.endswith(ext))
        raise LookupError(f"no {kind} file {name!r}; known: {known}")
    return path


def load_config(name: str) -> dict:
    with open(_lookup("configs", name, ".json")) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    with open(_lookup("traffic", name, ".json")) as f:
        return json.load(f)


def load_metric(name: str):
    """The ``read(summary)`` function of a per-layer metric."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        _lookup("metrics", name, ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    """The cell ``name`` with the metrics it reports: ``end_to_end`` and
    ``per_layer`` lists of metric entries."""
    for w in bench["workloads"]:
        if w["name"] == name:
            mine = lambda ms: [m for m in ms
                               if name in m.get("workloads", [name])]
            return dict(w, end_to_end=mine(bench["end_to_end"]),
                        per_layer=mine(bench["per_layer"]))
    raise LookupError(f"no workload {name!r}; known: "
                      f"{[w['name'] for w in bench['workloads']]}")


# ---------------------------------------------------------------------------
# JAX set-up and compile accounting
# ---------------------------------------------------------------------------

def configure_jax():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # every program, however small, is cached, so a warm set-up compiles
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the original threefry layout: seed 0 draws the golden plans' fleet
    jax.config.update("jax_threefry_partitionable", False)
    jax.config.update("jax_enable_x64", True)


class CompileClock:
    """Programs got ready (compiled, or loaded from the persistent cache),
    and the seconds spent tracing, lowering, compiling and loading."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              # around the compile or the cache load, both
              "/jax/core/compile/backend_compile_duration": "backend_s",
              "/jax/compilation_cache/cache_retrieval_time_sec": "load_s"}

    def __init__(self):
        import jax

        self.programs, self.hits = 0, 0
        self.secs = dict.fromkeys(self.EVENTS.values(), 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.secs[self.EVENTS[event]] += duration
            self.programs += event == "/jax/core/compile/backend_compile_duration"

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.hits,
                **self.secs}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _machine_cpu() -> tuple:
    """Seconds that all the machine's cores spent idle and stolen (taken
    by the hypervisor), from /proc/stat; zeros where it cannot say."""
    try:
        with open("/proc/stat") as f:
            cols = [float(x) for x in f.readline().split()[1:9]]
        tick = os.sysconf("SC_CLK_TCK")
        return cols[3] / tick, cols[7] / tick
    except (OSError, ValueError, IndexError):
        return 0.0, 0.0


class HostClock:
    """What the host did over a span: the process's and the calling
    thread's CPU seconds, page faults, context switches, the seconds
    spent in garbage collection, and the machine's idle and stolen core
    seconds."""

    def __init__(self):
        self.gc_s = 0.0
        self._gc_t0 = None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def close(self):
        gc.callbacks.remove(self._gc)

    def read(self) -> tuple:
        me = resource.getrusage(resource.RUSAGE_SELF)
        th = resource.getrusage(resource.RUSAGE_THREAD)
        return (time.perf_counter(), me.ru_utime + me.ru_stime,
                th.ru_utime + th.ru_stime, me.ru_minflt, me.ru_majflt,
                me.ru_nvcsw, me.ru_nivcsw, self.gc_s, *_machine_cpu())

    @staticmethod
    def delta(a: tuple, b: tuple) -> dict:
        d = [y - x for x, y in zip(a, b, strict=True)]
        return {"s": d[0], "cpu_s": d[1], "thread_cpu_s": d[2],
                "minflt": d[3], "majflt": d[4], "vcsw": d[5], "ivcsw": d[6],
                "gc_s": d[7], "machine_idle_s": d[8], "machine_steal_s": d[9]}


def run_window(cell, seconds: float, trace_requests: int = 0,
               trace_dir: str | None = None):
    """Closed loop from request 0 until ``seconds`` have passed; the first
    ``trace_requests`` requests are profiled into ``trace_dir``. Returns
    (answers by request index, seconds to the end of the last request,
    each request's host record: ``HostClock.delta`` and its split)."""
    import jax

    answers, took = {}, []
    host = HostClock()
    t0 = time.perf_counter()
    end = t0
    last = host.read()

    def one():
        nonlocal end, last
        i = len(took)
        answers[i] = cell.request(i)
        now = host.read()
        rec = HostClock.delta(last, now)
        rec["draw_plan_fetch_s"] = list(cell.last_split)
        took.append(rec)
        end, last = now[0], now

    if trace_requests:
        cell.trace_spans = True
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            while len(took) < trace_requests and end - t0 < seconds:
                one()
        jax.profiler.stop_trace()
        cell.trace_spans = False
        last = host.read()
        end = last[0]
    while time.perf_counter() - t0 < seconds:
        one()
    host.close()
    return answers, end - t0, took


def check_sample(answers: dict, seed: int, size: int) -> list:
    """The request indices to compare: ``size`` of them drawn from the
    seed (all, where the window made fewer)."""
    keys = sorted(answers)
    if len(keys) <= size:
        return keys
    rng = np.random.default_rng(seed)
    return sorted(rng.choice(keys, size=size, replace=False).tolist())


def _memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell_entry: dict, seed: int, seconds: float, trace: bool,
             devices, out=sys.stdout) -> dict:
    """One run of a cell on ``devices`` (a list); returns the result
    object (the caller has checked the devices and configured JAX)."""
    from chipbench import cell as cell_mod
    from chipbench import check, trace as trace_mod

    clock = CompileClock()
    config = load_config(cell_entry["config"])
    traffic = load_traffic(cell_entry["traffic"])
    readers = {m["name"]: load_metric(m["name"]) for m in cell_entry["per_layer"]}
    t_build0 = time.perf_counter()
    cell = cell_mod.Cell(config, traffic, seed, devices)
    t_warm0 = time.perf_counter()
    c0 = clock.snapshot()
    cell.request(-1 & 0x7FFFFFFF)  # warm-up: a request no window makes
    t_warm1 = time.perf_counter()
    setup_s = _since_process_start()
    c1 = clock.snapshot()
    warm = {k: c1[k] - c0[k] for k in c1}
    print(json.dumps({"setup_split_s": {
        "total": setup_s,
        "import_and_runtime": setup_s - (t_warm1 - t_build0),
        "fleet_build": t_warm0 - t_build0,
        "warmup_request": t_warm1 - t_warm0,
        "of_which_trace": warm["trace_s"], "of_which_lower": warm["lower_s"],
        "of_which_compile": warm["backend_s"] - warm["load_s"],
        "of_which_cache_load": warm["load_s"],
        "programs": warm["programs"], "cache_hits": warm["cache_hits"]}}),
        file=out, flush=True)

    programs0 = clock.programs
    trace_dir = None
    n_trace = 0
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        n_trace = int(traffic["trace_requests"])
    answers, span_s, took = run_window(cell, seconds, n_trace, trace_dir)
    compiles_in_window = clock.programs - programs0
    memory_peak = _memory_peak(devices)
    n = len(answers)
    failed = int(sum(int(a.status != 0) for a in answers.values()))
    secs = [r["s"] for r in took]
    print(json.dumps({"window": {
        "requests": n, "span_s": span_s,
        "request_s_min_median_max": [min(secs), float(np.median(secs)),
                                     max(secs)] if secs else None,
        "compiles_in_window": compiles_in_window}}), file=out, flush=True)
    print(json.dumps({"requests": took}), file=out, flush=True)

    metrics = {}
    breakdown = None
    device_info = {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": memory_peak}
    if trace:
        path = _find_xplane(trace_dir)
        xplane_bytes = os.path.getsize(path)
        summary = trace_mod.summarize(trace_mod.read_events(path))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in cell_entry["per_layer"]:
            v = readers[m["name"]](summary)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device_info.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.device_ops,
                     "idle_gaps": summary.idle_gaps}
        print(json.dumps({"trace": {"requests": summary.requests,
                                    "xplane_bytes": xplane_bytes,
                                    "modules": summary.modules}}),
              file=out, flush=True)
    else:
        values = {"setup_s": setup_s, "plan_s": span_s / n if n else None}
        for m in cell_entry["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    # the comparison, after the window: the plans are on the host, the
    # device holds nothing of the program's
    sample = check_sample(answers, seed, int(traffic["check_sample"]))
    t_check0 = time.perf_counter()
    numbers = check.check_answers(
        config, cell.scenario, {i: answers[i] for i in sample},
        lambda i: np.asarray(cell.gains(i)))
    print(json.dumps({"comparison": {
        "requests": len(sample), "s": time.perf_counter() - t_check0,
        "host_peak_rss_bytes":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}}),
        file=out, flush=True)
    correct = n > 0 and check.verdict(numbers)
    result = {"correct": bool(correct), "attempted": n, "failed": failed,
              "metrics": metrics, "device": device_info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check.report(numbers)
    return result


def _find_xplane(trace_dir: str) -> str:
    for dirpath, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def start(workload: str):
    """The start of every run: the program is there, JAX sees the chips
    that the cell asks for, and JAX is configured. Returns ``(cell entry,
    the cell's devices)``: the first ``chips`` that JAX lists, or the exit
    code where nothing may run."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chipbench: no program to run ({src}/repro is missing)",
              file=sys.stderr)
        return 1
    cell_entry = cell_of(load_benchmark(), workload)

    import jax

    devices = jax.devices()
    chips = int(cell_entry["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: needs {chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s). Nothing was run.",
              file=sys.stderr)
        return NO_CHIP
    sys.path.insert(0, src)
    configure_jax()
    return cell_entry, devices[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    started = start(args.workload)
    if isinstance(started, int):
        return started
    cell_entry, devices = started
    result = run_cell(cell_entry, args.seed, args.seconds, bool(args.trace),
                      devices)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"check correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
