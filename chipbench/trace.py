"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
per-layer numbers.

A traced run wraps its traced requests in the host span
``chipbench.window`` and each request's steps in ``chipbench.draw``,
``chipbench.plan`` and ``chipbench.fetch`` (``jax.profiler.TraceAnnotation``).
From the trace this module takes, inside that window:

- the device busy intervals: the union of the program executions on
  each device plane (``XLA Modules``; every device op runs inside one),
  busy seconds averaged over the device planes;
- the device seconds and the number of executions of each XLA module,
  on each device plane; the program's per request are those of the
  busiest plane, the chip that sets the pace (``program_modules``), and
  the breakdown's are averaged over the planes;
- the idle gaps between busy intervals, each labelled by the host span
  in which its midpoint fell (``host`` where none did), summed by label.

The per-op events (``XLA Ops``, millions for one plan: every step of
every loop) are not read. Events are read with
``jax.profiler.ProfileData``; ``summarize`` works on plain ``Event``
tuples so that it can be checked on a recorded trace.
"""
from __future__ import annotations

from collections import defaultdict
from typing import NamedTuple

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
DEVICE_PREFIX = "/device:"
MODULE_LINE = "XLA Modules"
TOP = 10
#: XLA modules of the benchmark's own (the fleet draw), left out of the
#: program's device time and launch count
OWN_MODULES = ("jit_draw_gains",)


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


class Summary(NamedTuple):
    window_s: float
    busy_s: float  # averaged over the device planes
    devices: int
    modules: dict  # module name -> [executions, device seconds], all planes
    device_ops: list  # [[module name, seconds a plane]], most time first
    idle_gaps: list  # [[host span, seconds a plane]], most idle time first
    requests: int  # chipbench.plan spans that began inside the window
    plane_modules: dict  # device plane -> {module name: [executions, s]}


def read_events(path: str) -> list[Event]:
    """The events of every plane and line of the trace at ``path``."""
    from jax.profiler import ProfileData

    return events_of(ProfileData.from_file(path))


def events_of(profile) -> list[Event]:
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PREFIX) and line.name != MODULE_LINE:
                continue
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def summarize(events) -> Summary:
    """The per-layer numbers of the traced window in ``events``."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo = windows[0].start_ns
    hi = lo + windows[0].dur_ns
    inside = lambda e: e.start_ns < hi and e.start_ns + e.dur_ns > lo

    dev = [e for e in events if e.plane.startswith(DEVICE_PREFIX)
           and e.line == MODULE_LINE and inside(e)]
    planes = sorted({e.plane for e in dev})
    busy_ns, per_plane = 0.0, {}
    modules = defaultdict(lambda: [0, 0.0])
    plane_modules = {}
    for p in planes:
        runs = [(e.name, *_clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi))
                for e in dev if e.plane == p]
        mine = plane_modules[p] = defaultdict(lambda: [0, 0.0])
        for name, s, t in runs:
            for tally in (modules[name], mine[name]):
                tally[0] += 1
                tally[1] += (t - s) * 1e-9
        per_plane[p] = union((s, t) for _, s, t in runs)
        busy_ns += sum(t - s for s, t in per_plane[p])

    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
             and e.name != WINDOW_SPAN and not e.plane.startswith(DEVICE_PREFIX)]
    gaps = defaultdict(float)
    for iv in per_plane.values():
        edges = [lo] + [x for pair in iv for x in pair] + [hi]
        for s, t in zip(edges[::2], edges[1::2]):
            if t <= s:
                continue
            mid = 0.5 * (s + t)
            label = "host"
            for sp in spans:
                if sp.start_ns <= mid < sp.start_ns + sp.dur_ns:
                    label = sp.name[len(SPAN_PREFIX):]
            gaps[label] += (t - s) * 1e-9
    requests = sum(1 for e in spans if e.name == SPAN_PREFIX + "plan"
                   and lo <= e.start_ns < hi)
    n_dev = max(len(planes), 1)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9 / n_dev,
        devices=len(planes),
        modules={k: list(v) for k, v in modules.items()},
        device_ops=[[k, v[1] / n_dev] for k, v in
                    sorted(modules.items(), key=lambda kv: -kv[1][1])[:TOP]],
        idle_gaps=[[k, v / n_dev] for k, v in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]],
        requests=requests,
        plane_modules={p: {k: list(v) for k, v in m.items()}
                       for p, m in plane_modules.items()})


def program_modules(s: Summary) -> tuple[int, float]:
    """(executions, device seconds) of the program's modules in the
    window, every module but the benchmark's own, on the busiest device
    plane: the one with the most such seconds (then executions)."""
    best = (0.0, 0)
    for mods in s.plane_modules.values():
        runs = [v for k, v in mods.items()
                if not any(k.startswith(own) for own in OWN_MODULES)]
        best = max(best, (sum(v[1] for v in runs), sum(v[0] for v in runs)))
    return best[1], best[0]
