"""Find an open-loop cell's knee: its traffic at rising mean rates, one
process, one set-up.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --rates 2,3,4,5 --seconds 15

For each rate it prints the requests due in the window, those completed
inside it, the mean backlog (requests sent and not yet resolved) over the
first and the last third of the window, p50 / p95 client latency, and
the process's stalls inside the window, one JSON line per rate. The knee is the highest rate whose backlog does
not grow over the window. Needs a TPU.
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


def backlog(records, t: float) -> int:
    return sum(1 for r in records if r.t_sub <= t
               and (r.t_done is None or r.t_done > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    from chipbench import cell as C
    from chipbench import spec, traffic
    cell = spec.load(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU; nothing run", file=sys.stderr)
        return 1
    rates = [float(x) for x in args.rates.split(",")]
    tr = cell.traffic
    lead = float(tr["lead_s"])
    horizon = lead + args.seconds + C.TAIL_S
    su = C.prepare(cell, seed=args.seed, horizon=horizon, trace=False,
                   t_start=T_START, root=ROOT)
    lens = set()
    for rate in rates:
        t_r = dict(tr, arrival=dict(tr["arrival"], rate_per_s=rate))
        lens |= set(traffic.lengths(t_r, traffic.n_requests(t_r, horizon)))
    C._warm(su.eng, su.cfg, tr, sorted(lens), su.params)
    for rate in rates:
        t_r = dict(tr, arrival=dict(tr["arrival"], rate_per_s=rate))
        sends = traffic.schedule(t_r, su.t_seed, horizon)
        ld = C.serve_load(su, sends, lead=lead, seconds=args.seconds)
        run = C.Run(cell=cell, open_loop=True, seconds=args.seconds,
                    t0=ld.t0, t1=ld.t1, setup_s=0.0, records=ld.records,
                    group_steps=ld.group_steps, model=su.m, peaks={},
                    drain_end=ld.drain_end)
        lat = C.latencies(run)
        third = args.seconds / 3
        probe = [ld.t0 + i * 0.25 for i in range(int(args.seconds * 4))]
        first = [backlog(ld.records, t) for t in probe if t < ld.t0 + third]
        last = [backlog(ld.records, t) for t in probe
                if t >= ld.t1 - third]
        done = [r for r in ld.records
                if r.t_done is not None and ld.t0 <= r.t_done < ld.t1]
        row = {"rate_per_s": rate, "due": len(lat),
               "completed_in_window": len(done),
               "backlog_first_third": sum(first) / max(1, len(first)),
               "backlog_last_third": sum(last) / max(1, len(last)),
               "p50_s": C.percentile(lat, 50), "p95_s": C.percentile(lat, 95),
               "programs_in_window": ld.programs,
               "stalls": len(ld.stalls),
               "stalled_s": sum(s.wall_s for s in ld.stalls)}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
