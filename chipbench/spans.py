"""The program's own host spans in a profiler trace, and the numbers of
the sharded price loop read from them.

The planner marks its host loop with ``jax.profiler.TraceAnnotation``s
named ``repro.*``: ``repro.plan_sharded`` holds ``repro.build_groups``
and one ``repro.step`` per alternation step (the last one the final λ
clearing and the finish); a step holds its price clearings,
``repro.price.lam`` and ``repro.price.mu``; a clearing holds its probes,
``repro.price.probe``, one host round trip each; a probe holds
``repro.price.wait``, the host blocked on the device's partial sum and
its copy back. From the events of a trace
(``chipbench.trace.read_events``) this module takes, inside the one
``chipbench.window`` span:

- ``table``: for each ``repro.`` span name that starts in the window,
  [count, seconds, self seconds]; self time is the duration minus the
  union of its child ``repro.`` spans on the same plane and line;
- ``program_idle_gaps``: the device's idle gaps as
  ``chipbench.trace.summarize`` finds them, each labelled by the
  innermost ``repro.`` or ``chipbench.`` span holding its midpoint;
- per plan request: the probes of the price loop, the milliseconds it
  waited on the device, and its host milliseconds besides the waits.

``chipbench.run`` does not read these yet: its ``Summary`` carries no
span table. On the chip,

    python3 -m chipbench.spans --workload <cell> --seed <n> --seconds <s>

run from the root of a checkout, sets up as ``chipbench.run`` does,
traces the cell's first ``trace_requests`` requests, runs the rest of
the window untraced, and prints one JSON line: the trace's numbers
(those of the accepted readers and these) and every request's seconds.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from collections import defaultdict

from chipbench import trace

PREFIX = "repro."
PROBE = "repro.price.probe"
WAIT = "repro.price.wait"
CLEARINGS = ("repro.price.lam", "repro.price.mu")


def window(events) -> tuple[float, float]:
    """[start, end) ns of the one ``chipbench.window`` span."""
    found = [e for e in events if e.name == trace.WINDOW_SPAN]
    if len(found) != 1:
        raise ValueError(f"expected one {trace.WINDOW_SPAN} span, "
                         f"found {len(found)}")
    return found[0].start_ns, found[0].start_ns + found[0].dur_ns


def _host(e) -> bool:
    return not e.plane.startswith(trace.DEVICE_PREFIX)


def _end(e) -> float:
    return e.start_ns + e.dur_ns


def table(events) -> dict:
    """{span name: [count, seconds, self seconds]} of the ``repro.`` host
    spans that start inside the window."""
    lo, hi = window(events)
    spans = sorted((e for e in events if _host(e) and e.name.startswith(PREFIX)
                    and lo <= e.start_ns < hi),
                   key=lambda e: (e.plane, e.line, e.start_ns, -e.dur_ns))
    # sorted so, a span's parent is the nearest open span that holds it
    holds = lambda p, e: ((p.plane, p.line) == (e.plane, e.line)
                          and _end(e) <= _end(p))
    children = defaultdict(list)
    stack = []
    for i, e in enumerate(spans):
        while stack and not holds(spans[stack[-1]], e):
            stack.pop()
        if stack:
            children[stack[-1]].append((e.start_ns, _end(e)))
        stack.append(i)
    out = defaultdict(lambda: [0, 0.0, 0.0])
    for i, e in enumerate(spans):
        covered = sum(t - s for s, t in trace.union(children[i]))
        row = out[e.name]
        row[0] += 1
        row[1] += e.dur_ns * 1e-9
        row[2] += (e.dur_ns - covered) * 1e-9
    return dict(out)


def program_idle_gaps(events) -> list:
    """[[label, seconds]] of the device's idle time in the window, most
    first: each gap labelled by the innermost ``repro.`` or
    ``chipbench.`` host span holding its midpoint (``chipbench.``
    dropped from the name, as ``Summary.idle_gaps`` has it; ``host``
    where none holds it), averaged over the device planes."""
    lo, hi = window(events)
    per_plane = defaultdict(list)
    for e in events:
        if (e.plane.startswith(trace.DEVICE_PREFIX)
                and e.line == trace.MODULE_LINE
                and e.start_ns < hi and _end(e) > lo):
            per_plane[e.plane].append((max(e.start_ns, lo), min(_end(e), hi)))
    labels = [e for e in events if _host(e) and e.name != trace.WINDOW_SPAN
              and e.name.startswith((PREFIX, trace.SPAN_PREFIX))]
    gaps = defaultdict(float)
    for intervals in per_plane.values():
        busy = trace.union(intervals)
        edges = [lo] + [x for pair in busy for x in pair] + [hi]
        for s, t in zip(edges[::2], edges[1::2], strict=True):
            if t <= s:
                continue
            mid = 0.5 * (s + t)
            held = [sp for sp in labels if sp.start_ns <= mid < _end(sp)]
            label = "host"
            if held:
                label = min(held, key=lambda sp: sp.dur_ns).name
                label = label.removeprefix(trace.SPAN_PREFIX)
            gaps[label] += (t - s) * 1e-9
    n_dev = max(len(per_plane), 1)
    return [[k, v / n_dev] for k, v in
            sorted(gaps.items(), key=lambda kv: -kv[1])[:trace.TOP]]


def price_probes_per_plan(spans: dict, requests: int):
    """Host round trips of the price loop per plan request."""
    probes = spans.get(PROBE, [0])[0]
    if not requests or not probes:
        return None
    return probes / requests


def price_wait_ms(spans: dict, requests: int):
    """Milliseconds per plan request that the price loop waited on the
    device's partial sums."""
    if not requests or WAIT not in spans:
        return None
    return spans[WAIT][1] * 1e3 / requests


def price_host_ms(spans: dict, requests: int):
    """Milliseconds per plan request of the price clearings less their
    waits: the host's own time in the loop (copies, dispatch, numpy)."""
    clearing_s = sum(spans[c][1] for c in CLEARINGS if c in spans)
    if not requests or not clearing_s:
        return None
    return (clearing_s - spans.get(WAIT, [0, 0.0])[1]) * 1e3 / requests


def reduce(events) -> dict:
    """Every number this module and the accepted readers take from a
    trace, per plan request where a reader is."""
    from chipbench import run

    s = trace.summarize(events)
    spans = table(events)
    out = {"requests": s.requests, "window_s": s.window_s, "busy_s": s.busy_s}
    for name in ("launches_per_plan", "plan_program_ms", "idle_share.plan"):
        out[name] = run.load_metric(name)(s)
    for fn in (price_probes_per_plan, price_wait_ms, price_host_ms):
        out[fn.__name__] = fn(spans, s.requests)
    out.update(spans=spans, device_ops=s.device_ops, idle_gaps=s.idle_gaps,
               program_idle_gaps=program_idle_gaps(events))
    return out


def main(argv=None) -> int:
    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    started = run.start(args.workload)
    if isinstance(started, int):
        return started
    cell_entry, devices = started
    from chipbench import cell as cell_mod

    traffic = run.load_traffic(cell_entry["traffic"])
    cell = cell_mod.Cell(run.load_config(cell_entry["config"]), traffic,
                         args.seed, devices)
    cell.request(-1 & 0x7FFFFFFF)  # warm-up, as chipbench.run makes it
    trace_dir = tempfile.mkdtemp(prefix="chipbench_spans_")
    n_trace = int(traffic["trace_requests"])
    _, _, took = run.run_window(cell, args.seconds, n_trace, trace_dir)
    events = trace.read_events(run._find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    out = reduce(events)
    out.update(traced_requests=n_trace,
               request_s=[r["s"] for r in took],
               device={"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
