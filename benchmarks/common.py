"""Shared benchmark utilities. Every bench emits CSV rows
``name,us_per_call,derived`` (derived = the paper figure's metric).

``timed`` reports *steady-state* µs/call: the first call (jit compile) is
excluded by a warmup, every call is synced with ``jax.block_until_ready``
so device work is actually finished when the clock stops, and the result
is averaged over ``repeats``. Use ``timed_compile`` when the compile time
itself is part of the story (e.g. Fig. 11 cold vs warm).
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, List, NamedTuple, Tuple

import jax

Row = Tuple[str, float, str]

#: Machine-readable planner-perf artifact (repo root by default). Multiple
#: benches contribute sections via ``update_artifact`` so the perf
#: trajectory (ratio metrics, not raw wall-clock) accumulates in one file.
PLANNER_ARTIFACT = os.environ.get("BENCH_PLANNER_JSON", "BENCH_planner.json")


def update_artifact(section: str, payload: dict, path: str = None) -> None:
    """Read-modify-write ``payload`` under ``section`` in the JSON artifact."""
    path = PLANNER_ARTIFACT if path is None else path
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    if not isinstance(data, dict) or "rows" in data:  # pre-PR2 flat layout
        data = {}
    data[section] = payload
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def _sync(out):
    """Block until every array in ``out`` is materialized on device; a
    failure of the device computation raises here."""
    return jax.block_until_ready(out)


def timed(fn: Callable, repeats: int = 3, warmup: int = 1):
    """(out, steady_us): post-warmup, device-synced µs per call."""
    out = None
    for _ in range(max(warmup, 0)):
        out = _sync(fn())
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = _sync(fn())
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6  # µs


class Timing(NamedTuple):
    out: object
    compile_us: float  # first (cold) call — dominated by jit compile
    us: float  # steady-state per call


def timed_compile(fn: Callable, repeats: int = 3) -> Timing:
    """Like ``timed`` but also reports the cold first call separately."""
    t0 = time.perf_counter()
    out = _sync(fn())
    compile_us = (time.perf_counter() - t0) * 1e6
    out, us = timed(fn, repeats=repeats, warmup=0)
    return Timing(out=out, compile_us=compile_us, us=us)


def emit(rows: List[Row]) -> None:
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
