"""DEIS as a serving feature: streaming continuous-batching throughput.

Three measurements on a reduced backbone:

  * per-(solver, NFE) throughput -- serving capacity scales ~1/NFE, which is
    exactly why the paper's low-NFE quality matters operationally;
  * a mixed-traffic run: requests with different (solver, nfe, seq_len)
    admitted at different step boundaries, interleaved at NFE granularity by
    the streaming scheduler. The run asserts the compile cache stays at one
    trace per (plan.signature, batch, seq_len) -- no per-group recompilation
    -- and reports solve-only latency (compile time is tracked separately by
    the engine, so numbers are not poisoned by trace cost);
  * a mixed-PRIORITY ragged-NFE run under a throttled (EDF + aging)
    scheduler, once without and once with mid-flight group compaction. The
    ragged groups pad short plans to the bucket's longest grid, so without
    compaction every early-finished row burns one dead step per tick;
    compaction re-packs survivors into smaller cached batch buckets. The
    run reports p50/p99 request latency and ``wasted_row_steps``, asserts
    the wasted steps drop to zero under compaction, that both modes produce
    bitwise-identical per-request samples, and that the measured (warm)
    pass runs with ZERO recompilation -- compaction's shrunken batch sizes
    included, because they land in the same (signature, batch, seq_len)
    executor cache;
  * an EARLY-EXIT run: an engine under a RetirePolicy serves a mixed
    tab2/sndeis2/ddim workload; estimate-carrying rows retire once their
    embedded
    local-error estimate converges, and the run ratchets the (deterministic)
    early-exit count and saved NFEs at tol 0 -- the serving-side payoff of
    the embedded pairs;
  * a SHARDED mixed-traffic run on a forced 8-device host mesh (subprocess:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must be set
    before jax imports). Ragged request waves -- including stochastic rows
    with distinct seeds and a 12-request burst whose 16-row group compacts
    to 8 mid-flight UNDER sharding -- run through the request-axis sharded
    engine and through the single-device engine; the child asserts the two
    are bitwise identical per request and that the sharded warm pass runs
    with ZERO recompilation (compaction's shrunken multiples land in the
    same mesh-keyed (signature, batch, seq_len, mesh) executor cache).
"""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

import repro
from repro.configs.base import get_config
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine, Request


def _throughput_rows(eng, quick: bool):
    rows = []
    n_req = 4 if quick else 8
    for solver, nfe in ([("tab3", 5), ("tab3", 10), ("dpm3m", 10),
                         ("sndeis2", 10)] if quick else
                        [("ddim", 10), ("tab3", 5), ("tab3", 10), ("tab3", 20),
                         ("rho_heun", 5), ("dpm3m", 10), ("seeds2", 10),
                         ("scire2", 10), ("sndeis2", 10)]):
        reqs = [Request(uid=i, seq_len=32, nfe=nfe, solver=solver, seed=i)
                for i in range(n_req)]
        eng.serve(reqs)  # warm/compile
        t0 = time.perf_counter()
        res = eng.serve(reqs)
        dt = time.perf_counter() - t0
        assert all(r.compile_s == 0.0 for r in res), "warm serve recompiled"
        # report the TRUE evals spent (budgeted grids may round nfe down,
        # e.g. rho_heun at nfe=5 runs 4 evals) so ~1/NFE comparisons hold
        rows.append({"table": "deis_serving", "solver": solver,
                     "NFE": res[0].nfe, "requests": n_req,
                     "us_per_request": round(dt / n_req * 1e6, 1),
                     "seq_per_s": round(n_req / dt, 2)})
    return rows


def _mixed_traffic_row(eng, quick: bool):
    """Heterogeneous request waves admitted at different step boundaries."""
    waves = [
        [Request(uid=100 + i, seq_len=32, nfe=8, solver=s, seed=i)
         for i, s in enumerate(["ddim", "euler", "naive_ei", "ddim"])],
        [Request(uid=200 + i, seq_len=32, nfe=4, solver="tab2", seed=i)
         for i in range(2)],
        [Request(uid=300, seq_len=16, nfe=6, solver="em", seed=7),
         Request(uid=301, seq_len=16, nfe=6, solver="ddim_eta", eta=1.0,
                 seed=8)],
        # one request per next-gen family, all in one wave
        [Request(uid=500 + i, seq_len=32, nfe=6, solver=s, seed=20 + i)
         for i, s in enumerate(["dpm2m", "seeds1", "scire2", "sndeis2"])],
    ]
    if not quick:
        waves.append([Request(uid=400 + i, seq_len=32, nfe=8, solver="rho_heun",
                              seed=i) for i in range(2)])
    # warm every (signature, batch, seq_len) the waves will need
    for w in waves:
        eng.serve(list(w))
    executors_before = eng.num_executors

    results, steps = [], 0
    t0 = time.perf_counter()
    for w in waves:                      # admit each wave at a step boundary
        for r in w:
            eng.submit(r)
        results += eng.tick()            # interleaves with in-flight groups
        steps += 1
    while eng.busy:
        results += eng.tick()
        steps += 1
    dt = time.perf_counter() - t0

    n_req = sum(len(w) for w in waves)
    assert len(results) == n_req
    assert eng.num_executors == executors_before, (
        "mixed traffic caused recompilation beyond one trace per "
        "(plan.signature, batch, seq_len)")
    assert all(r.compile_s == 0.0 for r in results)
    return {"table": "deis_serving", "solver": "mixed", "NFE": "4-8",
            "requests": n_req, "scheduler_ticks": steps,
            "executors": eng.num_executors,
            "us_per_request": round(dt / n_req * 1e6, 1),
            "seq_per_s": round(n_req / dt, 2)}


def _ragged_priority_requests(quick: bool):
    """Mixed-priority, ragged-NFE workload: one ddim/euler family bucket per
    seq_len so admission builds ragged stacked groups. Deadlines/priorities
    are well separated so EDF ordering is deterministic across runs."""
    n_hi = 2 if quick else 4
    reqs = [Request(uid=i, seq_len=32, nfe=[4, 8, 12][i % 3],
                    solver=["ddim", "euler"][i % 2], seed=i, priority=0)
            for i in range(4 if quick else 8)]
    reqs += [Request(uid=100 + i, seq_len=32, nfe=4, solver="ddim",
                     seed=50 + i, priority=2, deadline_s=0.5)
             for i in range(n_hi)]
    return reqs


def _run_ragged(params, cfg, reqs, *, compaction: bool):
    """Two passes (cold compile, warm measure) of the ragged workload under a
    throttled EDF scheduler; returns (engine, warm results, latencies).

    Latency is END-TO-END per request (submit to Result emission), so it
    includes the queueing/skip delay the priority scheduler actually moves
    around -- ``Result.latency_s`` alone is solve-only and would hide it."""
    eng = DiffusionServeEngine(params, cfg, steps_per_tick=2, aging_ticks=4,
                               compaction=compaction, max_group=8)
    eng.serve(list(reqs))                 # cold: compile every bucket size
    eng.wasted_row_steps = 0
    eng.ticks = 0
    executors_before = eng.num_executors
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    results, e2e = [], []
    while eng.busy:
        for res in eng.tick():
            e2e.append(time.perf_counter() - t0)
            results.append(res)
    wall = time.perf_counter() - t0
    assert eng.num_executors == executors_before, (
        "warm ragged run recompiled: compaction bucket sizes must reuse the "
        "(signature, batch, seq_len) executor cache")
    assert all(r.compile_s == 0.0 for r in results)
    return eng, results, sorted(e2e), wall


def _ragged_priority_rows(params, cfg, quick: bool):
    reqs = _ragged_priority_requests(quick)
    rows, tokens = [], {}
    for compaction in (False, True):
        eng, results, lat, wall = _run_ragged(params, cfg, reqs,
                                              compaction=compaction)
        tokens[compaction] = {r.uid: r.tokens for r in results}
        p50 = lat[len(lat) // 2]
        p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
        rows.append({"table": "deis_serving",
                     "solver": "ragged_priority",
                     "compaction": compaction, "requests": len(reqs),
                     "scheduler_ticks": eng.ticks,
                     "wasted_row_steps": eng.wasted_row_steps,
                     "p50_ms": round(p50 * 1e3, 2),
                     "p99_ms": round(p99 * 1e3, 2),
                     "seq_per_s": round(len(reqs) / wall, 2)})
    # compaction must eliminate dead-row steps without changing any sample
    assert rows[1]["wasted_row_steps"] == 0 < rows[0]["wasted_row_steps"], (
        "compaction failed to reduce wasted row steps "
        f"({rows[0]['wasted_row_steps']} -> {rows[1]['wasted_row_steps']})")
    for uid in tokens[True]:
        np.testing.assert_array_equal(tokens[True][uid], tokens[False][uid])
    return rows


# ------------------------------------- continuous admission (joins) section
def _continuous_requests(quick: bool):
    """A staggered ragged-NFE stream in ONE ddim/euler family bucket: two
    requests arrive per tick, so by the time later waves land, earlier
    groups have retired rows -- exactly the boundary joins exploit."""
    n = 8 if quick else 16
    return [(i // 2, Request(uid=i, seq_len=32, nfe=[3, 6, 9][i % 3],
                             solver=["ddim", "euler"][i % 2], seed=i))
            for i in range(n)]


def _run_continuous(params, cfg, arrivals, *, continuous: bool):
    """Cold pass (compiles), then a warm measured pass of the staggered
    stream under a throttled scheduler. ``continuous`` enables joins
    into in-flight groups (+ compaction); off is the static-admission world
    where every wave forms its own group and dead rows ride along.

    Queue wait is per-request end-to-end time MINUS its solve latency
    (``Result.latency_s`` counts from the row's own admission), i.e. the
    time the scheduler left the request waiting -- pending, skipped, or
    riding unselected groups."""
    eng = DiffusionServeEngine(params, cfg, steps_per_tick=2, aging_ticks=4,
                               max_group=4, compaction=continuous,
                               join=continuous)

    arrival_tick = {r.uid: at for at, r in arrivals}

    def run():
        pending = sorted(arrivals, key=lambda a: a[0])
        i, t = 0, 0
        t0 = time.perf_counter()
        sub_t, results, e2e, wait_ticks = {}, [], {}, {}
        while i < len(pending) or eng.busy:
            while i < len(pending) and pending[i][0] <= t:
                sub_t[pending[i][1].uid] = time.perf_counter()
                eng.submit(pending[i][1])
                i += 1
            for res in eng.tick():
                e2e[res.uid] = time.perf_counter() - sub_t[res.uid]
                # scheduling delay in TICKS: completion tick minus arrival
                # tick minus the request's own step count (its floor). The
                # schedule is deterministic, so this metric is load- and
                # machine-independent -- what the mode comparison asserts
                # on (the wall-clock percentiles are reported, not
                # asserted: they flex with CPU contention).
                wait_ticks[res.uid] = (t - arrival_tick[res.uid] + 1
                                       - res.nfe)
                results.append(res)
            t += 1
        return results, e2e, wait_ticks, time.perf_counter() - t0

    run()                                   # cold: compile every bucket
    eng.wasted_row_steps = 0
    eng.ticks = 0
    eng.joined_requests = 0
    executors_before = eng.num_executors
    results, e2e, wait_ticks, wall = run()  # warm, measured
    assert eng.num_executors == executors_before, (
        "warm continuous-admission run recompiled: joined/compacted batches "
        "must reuse the (signature, batch, seq_len) executor cache")
    assert all(r.compile_s == 0.0 for r in results)
    waits = sorted(max(0.0, e2e[r.uid] - r.latency_s) for r in results)
    mean_wait_ticks = sum(wait_ticks.values()) / len(wait_ticks)
    return eng, results, waits, mean_wait_ticks, wall


def _continuous_admission_rows(params, cfg, quick: bool):
    arrivals = _continuous_requests(quick)
    rows, tokens, mean_wait = [], {}, {}
    for continuous in (False, True):
        eng, results, waits, wait_ticks, wall = _run_continuous(
            params, cfg, arrivals, continuous=continuous)
        tokens[continuous] = {r.uid: r.tokens for r in results}
        mean_wait[continuous] = wait_ticks
        rows.append({"table": "deis_serving",
                     "solver": "continuous_admission",
                     "joins": continuous, "requests": len(arrivals),
                     "scheduler_ticks": eng.ticks,
                     "joined_requests": eng.joined_requests,
                     "wasted_row_steps": eng.wasted_row_steps,
                     "mean_wait_ticks": round(wait_ticks, 2),
                     "mean_wait_ms": round(
                         sum(waits) / len(waits) * 1e3, 2),
                     "p50_wait_ms": round(waits[len(waits) // 2] * 1e3, 2),
                     "p99_wait_ms": round(
                         waits[min(len(waits) - 1,
                                   int(len(waits) * 0.99))] * 1e3, 2),
                     "warm_recompiles": 0,
                     "seq_per_s": round(len(arrivals) / wall, 2)})
    # continuous admission must cut both the (deterministic, tick-counted)
    # queue wait and the dead-row steps ...
    assert mean_wait[True] < mean_wait[False], (
        f"joins did not reduce mean scheduling delay "
        f"({mean_wait[False]:.2f} -> {mean_wait[True]:.2f} ticks)")
    assert rows[1]["wasted_row_steps"] == 0 < rows[0]["wasted_row_steps"]
    assert rows[1]["joined_requests"] > 0
    # ... without changing a single sample
    for uid in tokens[True]:
        np.testing.assert_array_equal(tokens[True][uid], tokens[False][uid])
    return rows


# -------------------------------------------------- early-exit (saved NFEs)
def _early_exit_rows(params, cfg, quick: bool):
    """Adaptive early-exit serving: an engine with a RetirePolicy retires
    rows whose embedded local-error estimate has converged, spending fewer
    NFEs than the request budgeted. The workload mixes estimate-carrying
    tab2 and sndeis2 (score-normalized pair, ``E * nu``) requests with
    pair-less ddim ones (which must always run their full budget).
    Early-exit counts and saved NFEs are deterministic
    functions of the seeded workload and the policy (the retire decision is
    per-row and timing-independent), so they ratchet at tol 0."""
    from repro.core.adaptive import RetirePolicy

    n = 6 if quick else 12
    reqs = [Request(uid=i, seq_len=32, nfe=[6, 9, 12][i % 3],
                    solver=("ddim" if i % 4 == 3 else
                            "sndeis2" if i % 4 == 1 else "tab2"), seed=i)
            for i in range(n)]
    eng = DiffusionServeEngine(params, cfg, steps_per_tick=2, max_group=4,
                               retire=RetirePolicy(tol=1.0, min_k=2))
    eng.serve(list(reqs))                  # cold: compile every bucket
    m = eng.metrics
    base_early = m.get("serve_early_exit_total").value
    base_saved = m.get("serve_saved_nfe_total").value
    executors_before = eng.num_executors
    t0 = time.perf_counter()
    results = eng.serve(list(reqs))        # warm, measured
    dt = time.perf_counter() - t0
    assert eng.num_executors == executors_before, (
        "warm early-exit run recompiled: estimate-carrying plans must reuse "
        "the (signature, batch, seq_len) executor cache")
    assert all(r.compile_s == 0.0 for r in results)

    by = {r.uid: r for r in results}
    budget = {q.uid: q.nfe for q in reqs}
    early = int(m.get("serve_early_exit_total").value - base_early)
    saved = int(m.get("serve_saved_nfe_total").value - base_saved)
    assert early == sum(r.early_exit for r in results) > 0
    assert saved == sum(budget[u] - by[u].nfe for u in by
                        if by[u].early_exit) > 0
    assert any(by[q.uid].early_exit for q in reqs if q.solver == "sndeis2"), (
        "no score-normalized (sndeis2) row early-exited under the policy")
    for q in reqs:                         # pair-less rows run their budget
        if q.solver == "ddim":
            assert not by[q.uid].early_exit and by[q.uid].nfe == q.nfe
    total = sum(budget.values())
    return [{"table": "deis_serving", "solver": "early_exit",
             "requests": len(reqs), "early_exits": early,
             "saved_nfe": saved, "budget_nfe": total,
             "nfe_saved_frac": round(saved / total, 3),
             "warm_recompiles": 0,
             "us_per_request": round(dt / len(reqs) * 1e6, 1),
             "seq_per_s": round(len(reqs) / dt, 2)}]


# ------------------------------------------------ sharded (8-device) section
# Runs in a child process because the forced host-device count only takes
# effect before jax is imported (this process already has 1 CPU device).
_SHARDED_CHILD = """
import json, time
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.configs.base import get_config
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine, Request
from repro.launch.mesh import make_request_mesh

QUICK = %(quick)r
cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
params = T.init_params(cfg, jax.random.PRNGKey(0))

# mixed traffic: a ragged deterministic burst (compacts 16 -> 8 mid-flight
# under sharding), plus a stochastic wave with distinct per-request seeds
reqs = [Request(uid=i, seq_len=16, nfe=[4, 8][i %% 2], solver="ddim", seed=i)
        for i in range(6 if QUICK else 12)]
reqs += [Request(uid=100 + i, seq_len=16, nfe=4, solver="em", seed=50 + i)
         for i in range(2 if QUICK else 3)]

base = DiffusionServeEngine(params, cfg, max_group=16)
want = {r.uid: r.tokens for r in base.serve(list(reqs))}

eng = DiffusionServeEngine(params, cfg, max_group=16, mesh=make_request_mesh())
eng.serve(list(reqs))                       # cold: compile every mesh bucket
executors = eng.num_executors
t0 = time.perf_counter()
res = eng.serve(list(reqs))                 # warm, measured
dt = time.perf_counter() - t0
got = {r.uid: r.tokens for r in res}

assert eng.num_executors == executors, "sharded warm serve recompiled"
assert all(r.compile_s == 0.0 for r in res)
batches = sorted({k[1] for k in eng._compiled})
assert all(b %% 8 == 0 for b in batches), batches   # groups place evenly
assert want.keys() == got.keys()
for uid in want:                            # bitwise vs single-device path
    np.testing.assert_array_equal(got[uid], want[uid])
print("ROWS " + json.dumps([{
    "table": "deis_serving", "solver": "sharded_8dev",
    "requests": len(reqs), "devices": jax.device_count(),
    "executor_batches": "/".join(str(b) for b in batches),
    "bitwise_vs_1dev": True, "warm_recompiles": 0,
    "us_per_request": round(dt / len(reqs) * 1e6, 1),
    "seq_per_s": round(len(reqs) / dt, 2)}]))
"""


def _sharded_rows(quick: bool):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    # repro may be a namespace package (no __init__), so resolve via __path__
    pkg_root = os.path.dirname(list(repro.__path__)[0])
    env["PYTHONPATH"] = os.pathsep.join(
        [pkg_root] + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD % {"quick": quick}],
        capture_output=True, text=True, timeout=1800, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded benchmark child failed:\n{out.stdout}\n{out.stderr}")
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("ROWS ")][-1]
    return json.loads(line[len("ROWS "):])


def run(quick: bool = False):
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = DiffusionServeEngine(params, cfg)
    rows = _throughput_rows(eng, quick)
    rows.append(_mixed_traffic_row(eng, quick))
    rows += _ragged_priority_rows(params, cfg, quick)
    rows += _continuous_admission_rows(params, cfg, quick)
    rows += _early_exit_rows(params, cfg, quick)
    rows += _sharded_rows(quick)
    return rows


# ------------------------------------------------- BENCH_serving.json record
def bench_metrics(rows: list[dict]) -> dict:
    """Convert run() rows into a named metric series for ``obs.bench``.

    Scheduler metrics that are deterministic functions of the (seeded)
    workload and the scheduling policy -- wasted row steps, tick counts,
    join counts, tick-denominated queue waits, warm recompiles, executor
    traces -- ratchet at tol 0: ANY drift is a scheduling regression (or an
    intentional policy change, in which case the committed baseline is
    updated in the same PR). Wall-clock timings ride along as
    ``ratchet: false`` trajectory points; they flex with the host."""
    from repro.obs.bench import metric

    out = {}
    for r in rows:
        sol = r["solver"]
        if sol == "ragged_priority":
            pre = ("ragged_priority.compaction_on" if r["compaction"]
                   else "ragged_priority.compaction_off")
            out[f"{pre}.wasted_row_steps"] = metric(
                r["wasted_row_steps"], unit="steps", ratchet=True, tol=0.0)
            out[f"{pre}.scheduler_ticks"] = metric(
                r["scheduler_ticks"], unit="ticks", ratchet=True, tol=0.0)
            out[f"{pre}.p50_ms"] = metric(r["p50_ms"], unit="ms")
            out[f"{pre}.p99_ms"] = metric(r["p99_ms"], unit="ms")
        elif sol == "continuous_admission":
            pre = ("continuous_admission.joins_on" if r["joins"]
                   else "continuous_admission.joins_off")
            out[f"{pre}.wasted_row_steps"] = metric(
                r["wasted_row_steps"], unit="steps", ratchet=True, tol=0.0)
            out[f"{pre}.joined_requests"] = metric(
                r["joined_requests"], unit="requests", direction="higher",
                ratchet=True, tol=0.0)
            out[f"{pre}.mean_wait_ticks"] = metric(
                r["mean_wait_ticks"], unit="ticks", ratchet=True, tol=0.0)
            out[f"{pre}.warm_recompiles"] = metric(
                r["warm_recompiles"], unit="compiles", ratchet=True, tol=0.0)
            out[f"{pre}.mean_wait_ms"] = metric(r["mean_wait_ms"], unit="ms")
        elif sol == "early_exit":
            out["early_exit.early_exits"] = metric(
                r["early_exits"], unit="requests", direction="higher",
                ratchet=True, tol=0.0)
            out["early_exit.saved_nfe"] = metric(
                r["saved_nfe"], unit="evals", direction="higher",
                ratchet=True, tol=0.0)
            out["early_exit.warm_recompiles"] = metric(
                r["warm_recompiles"], unit="compiles", ratchet=True, tol=0.0)
            out["early_exit.nfe_saved_frac"] = metric(
                r["nfe_saved_frac"], unit="frac", direction="higher")
            out["early_exit.us_per_request"] = metric(
                r["us_per_request"], unit="us")
        elif sol == "mixed":
            out["mixed.executors"] = metric(
                r["executors"], unit="traces", ratchet=True, tol=0.0)
            out["mixed.us_per_request"] = metric(
                r["us_per_request"], unit="us")
        elif sol == "sharded_8dev":
            out["sharded_8dev.warm_recompiles"] = metric(
                r["warm_recompiles"], unit="compiles", ratchet=True, tol=0.0)
            out["sharded_8dev.us_per_request"] = metric(
                r["us_per_request"], unit="us")
        else:  # per-(solver, NFE) throughput rows
            pre = f"throughput.{sol}_nfe{r['NFE']}"
            out[f"{pre}.us_per_request"] = metric(
                r["us_per_request"], unit="us")
            out[f"{pre}.seq_per_s"] = metric(
                r["seq_per_s"], unit="seq/s", direction="higher")
    return out


def main(argv=None) -> int:
    import argparse

    from .common import write_bench

    ap = argparse.ArgumentParser(prog="benchmarks.deis_serving")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="where to write the bench record (default "
                         "BENCH_serving.json in the cwd)")
    args = ap.parse_args(argv)
    rows = run(quick=args.quick)
    for r in rows:
        print(",".join(f"{k}={v}" for k, v in r.items()))
    write_bench("serving", bench_metrics(rows), args.out, quick=args.quick)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
