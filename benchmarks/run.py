"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME[,NAME..]]

Each module's run(quick) returns a list of dict rows; rows are printed as
``k=v`` CSV. A final ``name,us_per_call,derived`` summary line per table is
emitted for the harness contract.
"""
import argparse
import importlib
import time
import traceback

TABLES = [
    "fig3_parameterization",   # Fig. 3 (ingredients 1-2)
    "fig5_ablation",           # Fig. 5 / Tab. 9
    "table2_solvers",          # Tab. 2
    "table3_dpm",              # App. B Q5 / Tab. 3 (DPM-Solver comparison)
    "table4_ipndm",            # Tabs. 4-5
    "table6_schedules",        # Tabs. 6-8
    "table15_vesde",           # Tab. 15
    "cld_matrix",              # Sec. 2 matrix-coefficient generality (CLD)
    "nll_bench",               # App. B Q1
    "adaptive_bench",          # App. B Q2 (adaptive-step rejection waste)
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    names = args.only.split(",") if args.only else TABLES

    summary = []
    failed = []
    for name in names:
        print(f"\n===== {name} =====")
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        try:
            rows = mod.run(quick=args.quick)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
            summary.append((name, -1.0, f"ERROR:{type(e).__name__}"))
            continue
        dt = time.perf_counter() - t0
        for r in rows:
            print(",".join(f"{k}={v}" for k, v in r.items()))
        us = dt / max(1, len(rows)) * 1e6
        summary.append((name, us, f"rows={len(rows)}"))

    print("\nname,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
