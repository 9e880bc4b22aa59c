# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   PYTHONPATH=src python -m benchmarks.run [--only runtime,solver,...]
#
# ``--only`` entries match bench *module* names (substring) as before, and
# additionally the named *sections* a module exposes via a ``SECTIONS``
# dict (section name → zero-arg runner, declared in ``MODULE_SECTIONS``
# below so excluded modules are never imported) — so ``--only solver``
# runs just the solver A/B section of bench_runtime without the Fig.-11
# sweep, and ``--only runtime`` just the sweep without the A/B. For a
# module that declares sections, section matches take priority over a
# module-substring match (otherwise ``runtime`` could never select its
# section — it always substring-matches ``bench_runtime``); use the full
# module name (``--only bench_runtime``) to run such a module whole.
#
# Benches:
#   bench_fit           — Fig. 6   (NLS fit of t̄ = w/(g·f))
#   bench_convergence   — Fig. 9/10 (PCCP iterations; Alg.-2 trajectories)
#   bench_runtime       — Fig. 11  (runtime vs N; steady-state + compile,
#                         seed-loop speedup at N=50 → BENCH_planner.json)
#   bench_devices       — Fig. 12  (energy vs N; PCCP vs optimal) + the
#                         group-sharded scaling ladder to N=10⁵ devices
#                         (sharded-vs-monolithic ratio → BENCH_planner.json)
#   bench_risk_deadline — Fig. 13a/b, 14a/b (energy vs ε / deadline,
#                         one plan_grid call per sweep)
#   bench_violation     — Fig. 13c/14c (violation probability ≤ ε)
#   bench_plan_grid     — zipped 9-scenario plan_many vs sequential plans
#                         (+ seed-loop continuity ratio → BENCH_planner.json)
#   bench_hetero        — ragged mixed-model fleet: one compiled plan vs
#                         per-group sequential (ratios → BENCH_planner.json)
#   bench_edge          — shared-edge capacity pricing vs static N-scaling
#                         vs dedicated-VM (DESIGN.md §edge; energy at
#                         matched MC violation → BENCH_planner.json) + the
#                         E=3 multi-node placement A/B (priced Hybrid vs
#                         round-robin/greedy baselines + Cantelli ε_edge
#                         sweep → BENCH_planner.json §placement)
#   bench_faults        — closed-loop fault drill: guarded vs unguarded
#                         serving through an injected incident (DESIGN.md
#                         §robustness; recovery/churn → BENCH_planner.json)
#   bench_replay        — trace-driven replay: event-driven serving under
#                         a per-node brownout on the E=3 placement, with
#                         sentinel-triggered migration + regret vs a
#                         schedule-aware oracle (→ BENCH_planner.json
#                         §replay)
#   bench_two_tier      — beyond-paper: planner over zoo architectures
#   bench_channel       — beyond-paper: channel uncertainty + hetero fleet
#   bench_kernels       — Pallas kernels vs references
#   bench_roofline      — §Roofline terms from dry-run artifacts
from __future__ import annotations

import argparse
import os
import sys
import traceback

from benchmarks.common import emit

MODULES = [
    "bench_fit",
    "bench_convergence",
    "bench_runtime",
    "bench_devices",
    "bench_risk_deadline",
    "bench_violation",
    "bench_plan_grid",
    "bench_hetero",
    "bench_edge",
    "bench_faults",
    "bench_replay",
    "bench_two_tier",
    "bench_channel",
    "bench_kernels",
    "bench_roofline",
]

#: Named sections (module → section names) selectable via ``--only``
#: without running the whole module. Declared here — not discovered by
#: importing — so a filtered run never imports (and never fails on)
#: modules it was asked to exclude. Keep in sync with each module's
#: ``SECTIONS`` dict; bench_runtime asserts the two agree.
MODULE_SECTIONS = {
    "bench_runtime": ("runtime", "solver"),
    "bench_devices": ("fig12", "devices"),
    "bench_edge": ("edge", "placement"),
    "bench_replay": ("replay",),
}


def _configure_jax() -> None:
    """Compile cache where ``JAX_COMPILATION_CACHE_DIR`` says, else the
    fixed ``.jax_cache/`` at the repo root; the threefry bit layout the
    benches' seeded fleets and traces were drawn with."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache"))
    jax.config.update("jax_threefry_partitionable", False)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings of bench module names")
    args = ap.parse_args()
    wanted = args.only.split(",") if args.only else None
    _configure_jax()

    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        module_match = wanted is None or any(w in mod_name for w in wanted)
        section_match = [] if wanted is None else [
            s for s in MODULE_SECTIONS.get(mod_name, ())
            if any(w in s for w in wanted)]
        if not module_match and not section_match:
            continue  # excluded modules are never imported
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            if section_match:  # sections shadow module-substring matches
                for sec_name in section_match:
                    emit(mod.SECTIONS[sec_name]())
                continue
            emit(mod.run())
        except Exception:
            failures += 1
            print(f"{mod_name},0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
