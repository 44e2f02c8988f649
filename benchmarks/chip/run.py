"""Run one cell of the on-chip serving benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout. ``BENCHMARK.json`` names the cell's
configuration and traffic mix; everything else is found by those names
under ``benchmarks/chip/``. With ``--trace 0`` the last line of standard
output carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window. The numbers
that decide ``correct`` are printed beside their limits as the last lines
of standard error, and under ``compared`` in the result line.

``--control 1`` puts the float8 control in the program's place for the
comparison: such a run must read not correct. The benchmark's own runs
never use it.

The run needs a TPU: without one, or with fewer chips than the cell asks
for, it exits non-zero before building anything.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: compare the float8 control's tokens in the "
                    "program's place (the run must come out not correct)")
    return ap.parse_args(argv)


def main(argv=None, rehearsal=None) -> int:
    """``rehearsal`` (tests only): model overrides that shrink the cell so
    its code path runs on the CPU; it skips the look for a chip."""
    args = parse(argv)
    from chipbench import spec
    cell = spec.load(args.workload)
    import jax
    devices = jax.devices()
    if rehearsal is None:
        if devices[0].platform != "tpu":
            print(f"run.py: no TPU (JAX found {devices[0].platform}); "
                  "nothing run", file=sys.stderr)
            return 1
        if len(devices) < cell.chips:
            print(f"run.py: {args.workload} needs {cell.chips} chips, "
                  f"found {len(devices)}", file=sys.stderr)
            return 1
    from chipbench import cell as runner
    out = runner.run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START, root=ROOT,
                          rehearsal=rehearsal, control=bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
