"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. --full runs paper-scale sweeps;
the default quick mode keeps the whole suite to a few minutes on CPU.
"""

import argparse
import importlib
import sys
import time

MODULES = [
    "fig07_skew_cdf", "fig08_instances", "fig09_theta", "fig10_keydomain",
    "fig11_discretization", "fig12_fluctuation", "fig13_throughput",
    "fig14_realdata", "fig15_scaleout", "fig16_tpch", "fig17_table_size",
    "fig18_table_growth", "fig19_window", "fig20_beta",
    "moe_skewshield", "kernels_bench", "engine_fastpath", "planner_scaling",
    "sketch_scaling", "topology_pipeline", "strategy_matrix",
    "chaos_recovery",
]

#: the per-PR CI subset (--smoke): one representative module per subsystem —
#: single-stage engine figure, multi-stage topology, the cross-strategy
#: matrix (which also asserts mixed/reference and pkg/potc parity per shape),
#: the sketch-vs-exact stats A/B (which asserts its theta-quality contract
#: per shape) and the chaos/recovery arms (which assert the recovery-
#: lossless contract per point)
SMOKE_MODULES = ["fig16_tpch", "topology_pipeline", "strategy_matrix",
                 "sketch_scaling", "chaos_recovery"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module filter")
    ap.add_argument("--smoke", action="store_true",
                    help="per-PR CI subset (quick mode, one module per "
                         "subsystem); mutually exclusive with --only")
    args = ap.parse_args()
    if args.smoke and args.only:
        print("# pass either --smoke or --only, not both", file=sys.stderr)
        sys.exit(2)
    mods = SMOKE_MODULES if args.smoke else MODULES if not args.only else [
        m for m in MODULES if any(o in m for o in args.only.split(","))]
    if args.only and not mods:
        print(f"# no module matches --only={args.only}", file=sys.stderr)
        sys.exit(2)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for mod_name in mods:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            for name, us, derived in mod.rows(quick=not args.full):
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{mod_name}/ERROR,0,{type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            failed.append(mod_name)
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        # non-zero exit so CI gates on the suite instead of silently passing
        print(f"# FAILED modules ({len(failed)}): {','.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
