"""Deterministic fuzz simulation of the serving scheduler.

Seeded random workloads -- arrival ticks, priorities, deadlines, NFE
budgets, seq_lens and solver names -- are driven through
``DiffusionServeEngine`` (and, in the slow tier, on an 8-device host
mesh), asserting the three invariants the scheduler is
contractually not allowed to trade away:

* **bitwise-vs-solo (same controller)**: every Result equals the same
  request served alone on an identically-configured engine -- scheduling
  (grouping, joining, compaction, priorities, timing) never changes WHAT a
  request computes. "Identically configured" includes the early-exit
  controller: an engine with a RetirePolicy is compared against a solo
  engine under the SAME policy, and must retire each row at the identical
  own-step with the identical sample and NFE (the retire decision is a pure
  per-row function of the row's own error estimate, and the estimate's Linf
  reduction is batch-composition independent);
* **zero warm recompiles**: replaying the workload on the warm engine adds
  no executors and charges no compile time (the fixed-executor-set
  contract continuous admission exists to protect);
* **starvation-freedom / liveness**: the simulation drains within a
  bounded number of ticks and every submitted request gets a Result.

Arrivals are keyed to tick indices and deadlines are coarsely separated,
so the schedule -- group composition, join decisions, executor set -- is
deterministic across replays; that is what makes the recompile assertion
meaningful.
"""
import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine, Request

# every solver generation in one stream: classic ab deterministic/stochastic
# and wide-ab families, plus one representative of each next-gen family
# (DPM-Solver multistep, SEEDS exponential SDE, SciRE rk, score-normalized
# DEIS with its extra nu coefficient key)
_SOLVERS = ["ddim", "euler", "em", "ddim_eta", "tab2",
            "dpm2m", "seeds1", "scire2", "sndeis2"]
_MAX_TICKS = 2000


@pytest.fixture(scope="module")
def diff_setup():
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _gen_workload(fuzz_seed: int, n: int):
    """Seed -> [(arrival_tick, Request)]: random solver/NFE/seq_len/seed/
    priority/deadline mixes. Deadlines come from a VERY coarse grid (60s
    apart, far beyond any run's wall-clock spread) so the EDF order -- and
    therefore group composition and the executor set -- is identical
    between the cold pass and the warm replay, which is what makes the
    zero-recompile assertion deterministic."""
    rng = np.random.RandomState(fuzz_seed)
    out = []
    for uid in range(n):
        solver = _SOLVERS[rng.randint(len(_SOLVERS))]
        out.append((int(rng.randint(0, 8)), Request(
            uid=uid,
            seq_len=int(rng.randint(5, 9)),          # buckets to 8
            nfe=int(rng.randint(3, 9)),
            solver=solver,
            eta=1.0 if solver == "ddim_eta" else None,
            seed=int(rng.randint(0, 100)),
            priority=int(rng.randint(0, 3)),
            deadline_s=float(rng.choice([30.0, 90.0]))
            if rng.rand() < 0.4 else None)))
    return out


def _drive(eng, workload):
    """Submit at arrival ticks, tick until drained; assert liveness."""
    pending = sorted(workload, key=lambda a: a[0])
    i, t, results = 0, 0, []
    while i < len(pending) or eng.busy:
        while i < len(pending) and pending[i][0] <= t:
            eng.submit(pending[i][1])
            i += 1
        results += eng.tick()
        t += 1
        assert t < _MAX_TICKS, "scheduler failed to drain (starvation?)"
    return {r.uid: r for r in results}


def _make_engine(params, cfg):
    return DiffusionServeEngine(params, cfg, steps_per_tick=2, aging_ticks=3,
                                max_group=3, seq_len_buckets=(8,))


@pytest.fixture(scope="module")
def solo_engine(diff_setup):
    """One solo-reference engine reused across cases (same bucket config as
    the fuzzed engines; its (sig, 1, seq) executors warm up once)."""
    params, cfg = diff_setup
    return DiffusionServeEngine(params, cfg, seq_len_buckets=(8,))


@pytest.mark.parametrize("fuzz_seed", [0, 1, 2, 3])
def test_fuzz_traffic_bitwise_vs_solo_and_warm_cache(diff_setup, solo_engine,
                                                     fuzz_seed):
    params, cfg = diff_setup
    workload = _gen_workload(fuzz_seed, n=10)
    eng = _make_engine(params, cfg)
    got = _drive(eng, workload)
    assert len(got) == len(workload)                 # every request answered
    assert eng.wasted_row_steps == 0                 # compaction/join cover all

    # bitwise-vs-solo: content is a pure function of
    # (solver, nfe, eta, seed, bucketed seq_len)
    for _, req in workload:
        solo = solo_engine.serve([Request(
            uid=req.uid, seq_len=req.seq_len, nfe=req.nfe, solver=req.solver,
            eta=req.eta, seed=req.seed)])[0]
        np.testing.assert_array_equal(solo.tokens, got[req.uid].tokens)
        assert got[req.uid].nfe == solo.nfe          # true per-request NFE
        assert got[req.uid].latency_s >= 0.0
        assert got[req.uid].queue_wait_s >= 0.0

    # zero warm recompiles: the replayed schedule is deterministic, so the
    # executor set is closed after one pass
    n_exec = eng.num_executors
    warm = _drive(eng, workload)
    assert eng.num_executors == n_exec, "warm fuzz replay recompiled"
    assert all(r.compile_s == 0.0 for r in warm.values())
    for uid in got:                                  # replay is bit-stable
        np.testing.assert_array_equal(warm[uid].tokens, got[uid].tokens)


@pytest.fixture(scope="module")
def moe_setup():
    """sdar-30b-a3b at a tiny size as one chip's share: 4 of 8 experts held
    (ids 2-5), top-2, dropless routing through the expert kernel."""
    import dataclasses
    cfg = get_config("sdar_30b_a3b").reduced().with_(objective="diffusion")
    cfg = cfg.with_(moe=dataclasses.replace(
        cfg.moe, num_experts=8, top_k=2, experts_held=4, expert_offset=2))
    return T.init_params(cfg, jax.random.PRNGKey(0)), cfg


def test_fuzz_traffic_moe_bitwise_vs_solo_and_warm_cache(moe_setup):
    """The fuzz case above on a dropless MoE eps-net: grouping,
    joins and compaction never change what a request computes, and the
    warm replay compiles nothing."""
    params, cfg = moe_setup
    solo = DiffusionServeEngine(params, cfg, seq_len_buckets=(8,))
    test_fuzz_traffic_bitwise_vs_solo_and_warm_cache(moe_setup, solo,
                                                     fuzz_seed=0)


def test_fuzz_joins_admit_into_inflight_groups(diff_setup):
    """Sanity on the fuzz harness itself: a continuous ragged stream (a
    short+long pair arriving every tick, so retired rows open slots while
    later pairs are still pending) actually exercises the join path --
    otherwise the fuzz cases above would never splice a joiner."""
    params, cfg = diff_setup
    nfes = [3, 9, 6, 9, 3, 6, 9, 3, 6, 3]
    workload = [(i // 2, Request(uid=i, seq_len=8, nfe=nfes[i],
                                 solver="ddim", seed=i))
                for i in range(10)]
    eng = _make_engine(params, cfg)
    got = _drive(eng, workload)
    assert len(got) == 10
    assert eng.joined_requests > 0


# ----------------------------------- early-exit serving (controller fuzz)
_EE_POLICY = dict(tol=1.0, min_k=2)   # loose: reduced-config estimates sit
                                      # well under 1.0 a step or two in


@pytest.fixture(scope="module")
def solo_engine_ee(diff_setup):
    """Solo reference under the SAME RetirePolicy as the fuzzed engines --
    the early-exit bitwise invariant is vs-solo-with-same-controller."""
    from repro.core.adaptive import RetirePolicy
    params, cfg = diff_setup
    return DiffusionServeEngine(params, cfg, seq_len_buckets=(8,),
                                retire=RetirePolicy(**_EE_POLICY))


@pytest.mark.parametrize("fuzz_seed", [0, 1, 2, 3])
def test_fuzz_early_exit_bitwise_vs_solo_same_controller(diff_setup,
                                                         solo_engine_ee,
                                                         fuzz_seed):
    """Early-exit fuzz: under a shared RetirePolicy, grouping/joining/
    compaction never change WHEN a row retires or WHAT it returns -- every
    Result (early-exit or natural) is bitwise the solo engine's, with the
    same nfe and early_exit flag; saved NFEs are conserved into the
    registry; and the estimate-carrying executors stay warm-cache closed."""
    from repro.core.adaptive import RetirePolicy
    params, cfg = diff_setup
    # guarantee embedded-pair traffic: the random mix plus a tab2 burst
    workload = _gen_workload(fuzz_seed, n=8)
    workload += [(i, Request(uid=100 + i, seq_len=8, nfe=6 + i % 3,
                             solver="tab2", seed=i)) for i in range(4)]
    eng = DiffusionServeEngine(params, cfg, steps_per_tick=2, aging_ticks=3,
                               max_group=3, seq_len_buckets=(8,),
                               retire=RetirePolicy(**_EE_POLICY))
    got = _drive(eng, workload)
    assert len(got) == len(workload)
    assert eng.wasted_row_steps == 0

    m = eng.metrics
    n_early = sum(r.early_exit for r in got.values())
    assert n_early > 0                            # the dimension is exercised
    assert m.get("serve_early_exit_total").value == n_early
    # early exits COMPLETE (conservation: they deliver a sample)
    assert m.get("serve_completed_total").value == len(workload)
    saved = m.get("serve_saved_nfe_total").value
    assert saved == sum(
        req.nfe - got[req.uid].nfe for _, req in workload
        if got[req.uid].early_exit)
    assert saved > 0

    for _, req in workload:
        res = got[req.uid]
        solo = solo_engine_ee.serve([Request(
            uid=req.uid, seq_len=req.seq_len, nfe=req.nfe, solver=req.solver,
            eta=req.eta, seed=req.seed)])[0]
        np.testing.assert_array_equal(solo.tokens, res.tokens)
        assert (solo.early_exit, solo.nfe) == (res.early_exit, res.nfe)
        # final_err is only ULP-stable across DIFFERENT executables (solo is
        # batch-1, the fuzz group batch-N: the E-combination fuses
        # differently per executable while tokens/nfe/exit-step stay exact)
        if solo.final_err is None or res.final_err is None:
            assert solo.final_err == res.final_err
        else:
            np.testing.assert_allclose(solo.final_err, res.final_err,
                                       rtol=1e-4)
        if res.early_exit:
            assert res.nfe < req.nfe and res.final_err <= _EE_POLICY["tol"]
        # pair-less solvers must always run their full budget
        if req.solver in ("ddim", "euler", "em", "ddim_eta", "seeds1"):
            assert not res.early_exit and res.nfe == req.nfe

    n_exec = eng.num_executors
    warm = _drive(eng, workload)
    assert eng.num_executors == n_exec, "warm early-exit replay recompiled"
    assert all(r.compile_s == 0.0 for r in warm.values())
    for uid in got:
        np.testing.assert_array_equal(warm[uid].tokens, got[uid].tokens)
        assert warm[uid].nfe == got[uid].nfe


@pytest.mark.parametrize("solver", ["sndeis2", "dpm2m", "scire2"])
def test_new_family_early_exit_via_retire_policy(diff_setup, solver):
    """The next-gen families with embedded pairs retire through the SAME
    RetirePolicy path as tab2 -- for sndeis that exercises the ``E * nu``
    normalized estimate end-to-end (the acceptance criterion that
    plan_sndeis early-exits where a pair exists). Early exits are bitwise
    vs a solo engine under the same controller, and pair-carrying rows
    spend fewer NFEs than budgeted."""
    from repro.core.adaptive import RetirePolicy

    params, cfg = diff_setup
    reqs = [Request(uid=i, seq_len=8, nfe=8 + 2 * (i % 2), solver=solver,
                    seed=i) for i in range(3)]
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(8,), max_group=4,
                               retire=RetirePolicy(**_EE_POLICY))
    got = {r.uid: r for r in eng.serve(list(reqs))}
    assert sum(r.early_exit for r in got.values()) > 0
    solo = DiffusionServeEngine(params, cfg, seq_len_buckets=(8,),
                                retire=RetirePolicy(**_EE_POLICY))
    for q in reqs:
        want = solo.serve([Request(uid=q.uid, seq_len=q.seq_len, nfe=q.nfe,
                                   solver=q.solver, seed=q.seed)])[0]
        res = got[q.uid]
        np.testing.assert_array_equal(want.tokens, res.tokens)
        assert (want.early_exit, want.nfe) == (res.early_exit, res.nfe)
        if res.early_exit:
            assert res.nfe < q.nfe and res.final_err <= _EE_POLICY["tol"]


# ------------------------------------------- cancellation (race-tolerant)
def _drive_with_cancels(eng, workload, cancels):
    """_drive plus cancel orders keyed to ticks: {tick: [uid, ...]}.
    Cancels are best-effort -- a request may legitimately finish first."""
    pending = sorted(workload, key=lambda a: a[0])
    i, t, results = 0, 0, []
    while i < len(pending) or eng.busy:
        while i < len(pending) and pending[i][0] <= t:
            eng.submit(pending[i][1])
            i += 1
        for uid in cancels.get(t, ()):
            eng.cancel(uid)
        results += eng.tick()
        t += 1
        assert t < _MAX_TICKS, "scheduler failed to drain (starvation?)"
    return {r.uid: r for r in results}


@pytest.mark.parametrize("fuzz_seed", [0, 1])
def test_fuzz_cancellation_conservation_and_survivors(diff_setup,
                                                      solo_engine, fuzz_seed):
    """Cancellation storms: every request gets exactly one outcome, the
    registry conserves requests (submitted == completed + cancelled), a
    cancelled request delivers no sample, and cancellation never perturbs a
    survivor (bitwise-vs-solo through the same take_rows recycle path as
    deadline eviction). Cancels of unknown/finished uids are no-ops."""
    params, cfg = diff_setup
    rng = np.random.RandomState(100 + fuzz_seed)
    workload = _gen_workload(fuzz_seed, n=10)
    # cancel a random third across the drain window; some orders will lose
    # the race with completion on purpose (no-op then)
    cancels: dict = {}
    targets = rng.choice(10, size=4, replace=False)
    for uid in targets:
        cancels.setdefault(int(rng.randint(0, 12)), []).append(int(uid))
    cancels.setdefault(0, []).append(999)         # never submitted: no-op
    eng = _make_engine(params, cfg)
    got = _drive_with_cancels(eng, workload, cancels)
    assert len(got) == len(workload)              # one outcome per request

    m = eng.metrics
    submitted = m.get("serve_submitted_total").value
    completed = m.get("serve_completed_total").value
    cancelled = m.get("serve_cancelled_total").value
    assert submitted == len(workload)
    assert completed + cancelled == submitted     # conservation
    assert cancelled == sum(r.cancelled for r in got.values())
    assert eng.cancel(999) is False               # unknown uid: no-op

    for _, req in workload:
        res = got[req.uid]
        if res.cancelled:
            assert req.uid in set(int(u) for us in cancels.values()
                                  for u in us)
            assert res.tokens.size == 0 and res.nfe == 0
        else:
            solo = solo_engine.serve([Request(
                uid=req.uid, seq_len=req.seq_len, nfe=req.nfe,
                solver=req.solver, eta=req.eta, seed=req.seed)])[0]
            np.testing.assert_array_equal(solo.tokens, res.tokens)


def test_driver_cancel_on_own_stream(diff_setup):
    """Through the driver, a cancelled request fails with Cancelled on ITS
    OWN handle (stream closed, driver alive), later submissions still
    compute bitwise-identical samples, and stats() conserves requests."""
    from repro.serving.driver import ServeDriver
    from repro.serving.engine import Cancelled

    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(8,))
    with ServeDriver(eng) as drv:
        # warm the executor so the cancel below races a real solve window
        drv.submit(Request(uid=990, seq_len=8, nfe=3, solver="ddim",
                           seed=0)).result(timeout=120)
        h1 = drv.submit(Request(uid=1, seq_len=8, nfe=400, solver="ddim",
                                seed=1))
        h2 = drv.submit(Request(uid=2, seq_len=8, nfe=3, solver="ddim",
                                seed=2))
        assert drv.cancel(1) is True
        with pytest.raises(Cancelled) as ei:
            h1.result(timeout=60)
        assert ei.value.result.cancelled and ei.value.result.tokens.size == 0
        res2 = h2.result(timeout=60)
        assert not res2.cancelled and res2.tokens.size > 0
        assert drv.cancel(1) is False          # already finished: no-op
        assert drv.cancel(777) is False        # never submitted: no-op
        # the driver survived and still serves, bitwise-stable
        late = drv.submit(Request(uid=3, seq_len=8, nfe=3, solver="ddim",
                                  seed=2))
        np.testing.assert_array_equal(late.result(timeout=60).tokens,
                                      res2.tokens)
        s = drv.stats()
        assert s["cancelled"] == 1
    s = drv.stats()
    assert s["in_flight"] == 0
    # driver-side conservation: all submissions resolved exactly once
    assert s["submitted"] == 4
    m = eng.metrics
    assert m.get("serve_completed_total").value + \
        m.get("serve_cancelled_total").value == s["submitted"]


# -------------------------------------- deadline enforcement (storm fuzz)
def _gen_deadline_storm(fuzz_seed: int, n: int):
    """Seed -> [(arrival_tick, Request)] with deadlines across the whole
    spectrum: None (best-effort), 1 microsecond (expired before any tick can
    admit it -> deterministic pending-shed), a few hundred ms (may expire
    mid-flight depending on host speed -- genuinely racy on purpose), and
    60 s (never expires inside a test run). The conservation and
    bitwise-vs-solo invariants below are schedule-independent, so the racy
    band is safe to fuzz."""
    rng = np.random.RandomState(fuzz_seed)
    out = []
    for uid in range(n):
        solver = _SOLVERS[rng.randint(len(_SOLVERS))]
        deadline = [None, 1e-6, 0.2, 60.0][rng.randint(4)]
        out.append((int(rng.randint(0, 6)), Request(
            uid=uid,
            seq_len=int(rng.randint(5, 9)),
            nfe=int(rng.randint(3, 9)),
            solver=solver,
            eta=1.0 if solver == "ddim_eta" else None,
            seed=int(rng.randint(0, 100)),
            priority=int(rng.randint(0, 3)),
            deadline_s=deadline)))
    return out


@pytest.mark.parametrize("fuzz_seed", [0, 1, 2, 3, 4, 5])
def test_fuzz_deadline_storm_conservation_and_survivors(diff_setup,
                                                        solo_engine,
                                                        fuzz_seed):
    """Deadline storms: every submitted request gets EXACTLY one outcome
    (sample or deadline_exceeded Result, never both, never neither), the
    registry conserves requests (submitted == completed + evicted), and
    eviction never perturbs a surviving request's sample (survivors stay
    bitwise-vs-solo -- eviction recycles rows through the same take_rows
    boundary path as normal retirement)."""
    params, cfg = diff_setup
    workload = _gen_deadline_storm(fuzz_seed, n=12)
    eng = DiffusionServeEngine(params, cfg, steps_per_tick=2, aging_ticks=3,
                               max_group=3, seq_len_buckets=(8,),
                               enforce_deadlines=True)
    got = _drive(eng, workload)
    assert len(got) == len(workload)          # one outcome per request
    assert sorted(got) == [r.uid for _, r in sorted(workload,
                                                    key=lambda a: a[1].uid)]

    m = eng.metrics
    submitted = m.get("serve_submitted_total").value
    completed = m.get("serve_completed_total").value
    evicted = m.get("serve_deadline_evicted_total").value
    assert submitted == len(workload)
    assert completed + evicted == submitted   # conservation
    assert completed == sum(not r.deadline_exceeded for r in got.values())
    assert evicted == sum(r.deadline_exceeded for r in got.values())

    for _, req in workload:
        res = got[req.uid]
        if res.deadline_exceeded:
            # only requests that HAD a finite deadline can be evicted, and
            # an evicted request delivers no sample
            assert req.deadline_s is not None and req.deadline_s < 60.0
            assert res.tokens.size == 0 and res.nfe == 0
            assert res.queue_wait_s >= 0.0 and res.latency_s >= 0.0
        else:
            solo = solo_engine.serve([Request(
                uid=req.uid, seq_len=req.seq_len, nfe=req.nfe,
                solver=req.solver, eta=req.eta, seed=req.seed)])[0]
            np.testing.assert_array_equal(solo.tokens, res.tokens)
    # microsecond deadlines can never outrun the first admission pass
    for _, req in workload:
        if req.deadline_s == 1e-6:
            assert got[req.uid].deadline_exceeded


def test_deadline_enforcement_off_keeps_advisory_behavior(diff_setup):
    """The default engine treats deadlines as ordering hints only: an
    already-expired deadline must still be served to completion."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(8,))
    res = eng.serve([Request(uid=0, seq_len=8, nfe=3, solver="ddim", seed=0,
                             deadline_s=1e-6)])[0]
    assert not res.deadline_exceeded
    assert res.tokens.size > 0
    assert eng.metrics.get("serve_deadline_evicted_total").value == 0


def test_driver_deadline_exceeded_on_own_stream_with_shed_conservation(
        diff_setup):
    """Through the driver, an evicted request fails with DeadlineExceeded on
    ITS OWN handle (event stream closed, driver alive and serving), sheds
    are counted, and the stats()/registry view conserves requests:
    driver_submitted == completed + deadline_evicted, and every submit call
    is either accepted or shed."""
    from repro.serving.driver import QueueFull, ServeDriver
    from repro.serving.engine import DeadlineExceeded

    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(8,),
                               enforce_deadlines=True)
    eng.serve([Request(uid=990, seq_len=8, nfe=3, solver="ddim", seed=0)])
    # the warm serve above already moved the engine's counters; conservation
    # below is asserted on deltas from here
    m = eng.metrics
    base_completed = m.get("serve_completed_total").value
    base_evicted = m.get("serve_deadline_evicted_total").value
    with ServeDriver(eng, max_pending=3) as drv:
        handles, n_submits = {}, 0
        for i in range(3):
            handles[i] = drv.submit(Request(
                uid=i, seq_len=8, nfe=3, solver="ddim", seed=i,
                deadline_s=1e-6 if i == 0 else None))
            n_submits += 1
        # the in-flight set is full: this one must shed with QueueFull
        extra = drv.submit(Request(uid=99, seq_len=8, nfe=3, solver="ddim",
                                   seed=9))
        n_submits += 1
        with pytest.raises(QueueFull):
            extra.result(timeout=5)

        with pytest.raises(DeadlineExceeded):
            handles[0].result(timeout=30)
        assert list(handles[0]) == []          # stream closed, no events
        for i in (1, 2):
            res = handles[i].result(timeout=30)
            assert not res.deadline_exceeded and res.tokens.size > 0
        # the driver survived the eviction and still serves
        late = drv.submit(Request(uid=100, seq_len=8, nfe=3, solver="ddim",
                                  seed=1))
        n_submits += 1
        # same (solver, nfe, seed, seq_len) as uid=1: scheduling after an
        # eviction still computes the same sample
        np.testing.assert_array_equal(late.result(timeout=30).tokens,
                                      handles[1].result().tokens)

        s = drv.stats()
        assert s["shed"] == 1
        assert s["submitted"] == n_submits - s["shed"]
    # drained: exact conservation (deltas exclude the warm-up serve)
    s = drv.stats()
    assert s["in_flight"] == 0
    completed = m.get("serve_completed_total").value - base_completed
    evicted = m.get("serve_deadline_evicted_total").value - base_evicted
    assert completed + evicted == s["submitted"]
    assert evicted == 1


# --------------------------------------- 8-device host mesh (subprocess)
_CHILD_FUZZ = """
import os
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.configs.base import get_config
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine, Request
from repro.launch.mesh import make_request_mesh

cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
params = T.init_params(cfg, jax.random.PRNGKey(0))

rng = np.random.RandomState(3)
# mixed-generation traffic UNDER sharding: classic names plus one
# representative per next-gen family (dpm multistep, seeds, scire, sn-deis
# with its nu coefficient leaf, which must shard like any other plan leaf)
workload = [(int(rng.randint(0, 5)), Request(
    uid=i, seq_len=int(rng.randint(5, 9)), nfe=int(rng.choice([3, 5, 7])),
    solver=["ddim", "dpm2m", "seeds1", "scire2", "sndeis2", "em"][i %% 6],
    seed=int(rng.randint(100)), priority=int(rng.randint(2))))
    for i in range(10)]

def drive(eng):
    pending = sorted(workload, key=lambda a: a[0])
    i, t, res = 0, 0, []
    while i < len(pending) or eng.busy:
        while i < len(pending) and pending[i][0] <= t:
            eng.submit(pending[i][1]); i += 1
        res += eng.tick(); t += 1
        assert t < 2000
    return {r.uid: r for r in res}

base = DiffusionServeEngine(params, cfg, max_group=16, seq_len_buckets=(8,))
want = drive(base)
eng = DiffusionServeEngine(params, cfg, max_group=16, seq_len_buckets=(8,),
                           mesh=make_request_mesh())
got = drive(eng)
assert want.keys() == got.keys()
for uid in want:                     # sharded fuzz == single-device fuzz
    np.testing.assert_array_equal(got[uid].tokens, want[uid].tokens)
assert eng.wasted_row_steps == 0     # join-slot/structural filler excluded
batches = sorted({k[1] for k in eng._compiled})
assert all(b %% 8 == 0 for b in batches), batches
n = eng.num_executors
again = drive(eng)
assert eng.num_executors == n, "warm sharded fuzz replay recompiled"
for uid in want:
    np.testing.assert_array_equal(again[uid].tokens, want[uid].tokens)
print("FUZZ_MESH_OK joined=%%d" %% eng.joined_requests)
"""


_CHILD_FUZZ_EE = """
import os
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.configs.base import get_config
from repro.core.adaptive import RetirePolicy
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine, Request
from repro.launch.mesh import make_request_mesh

cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
params = T.init_params(cfg, jax.random.PRNGKey(0))

rng = np.random.RandomState(7)
workload = [(int(rng.randint(0, 5)), Request(
    uid=i, seq_len=int(rng.randint(5, 9)), nfe=int(rng.choice([5, 7, 9])),
    solver=["tab2", "ddim", "tab2"][i %% 3],
    seed=int(rng.randint(100)), priority=int(rng.randint(2))))
    for i in range(10)]

def drive(eng):
    pending = sorted(workload, key=lambda a: a[0])
    i, t, res = 0, 0, []
    while i < len(pending) or eng.busy:
        while i < len(pending) and pending[i][0] <= t:
            eng.submit(pending[i][1]); i += 1
        res += eng.tick(); t += 1
        assert t < 2000
    return {r.uid: r for r in res}

pol = RetirePolicy(tol=1.0, min_k=2)
base = DiffusionServeEngine(params, cfg, max_group=16, seq_len_buckets=(8,),
                            retire=pol)
want = drive(base)
assert any(r.early_exit for r in want.values())   # dimension exercised
eng = DiffusionServeEngine(params, cfg, max_group=16, seq_len_buckets=(8,),
                           mesh=make_request_mesh(), retire=pol)
got = drive(eng)
assert want.keys() == got.keys()
for uid in want:   # sharded early-exit fuzz == single-device early-exit fuzz
    np.testing.assert_array_equal(got[uid].tokens, want[uid].tokens)
    assert got[uid].nfe == want[uid].nfe
    assert got[uid].early_exit == want[uid].early_exit
    # the estimate's weighted combination is only ULP-stable across the
    # sharded/unsharded EXECUTABLES (different fusion); decisions matched
    if want[uid].final_err is not None:
        np.testing.assert_allclose(got[uid].final_err, want[uid].final_err,
                                   rtol=1e-4)
m_b, m_s = base.metrics, eng.metrics
assert m_s.get("serve_saved_nfe_total").value == \\
    m_b.get("serve_saved_nfe_total").value
# bitwise-vs-solo-with-same-controller holds exactly under the SAME mesh:
# same executable family, same per-row estimate, same retire step
solo = DiffusionServeEngine(params, cfg, seq_len_buckets=(8,),
                            mesh=make_request_mesh(), retire=pol)
for _, req in workload:
    s = solo.serve([Request(uid=req.uid, seq_len=req.seq_len, nfe=req.nfe,
                            solver=req.solver, seed=req.seed)])[0]
    g = got[req.uid]
    np.testing.assert_array_equal(s.tokens, g.tokens)
    assert (s.nfe, s.early_exit, s.final_err) == \\
        (g.nfe, g.early_exit, g.final_err)
n = eng.num_executors
again = drive(eng)
assert eng.num_executors == n, "warm sharded early-exit replay recompiled"
print("FUZZ_MESH_EE_OK early=%%d" %%
      int(m_s.get("serve_early_exit_total").value))
"""


@pytest.mark.slow  # compiles sharded estimate-carrying executors
def test_fuzz_early_exit_sharded_8dev_bitwise():
    """The early-exit invariants hold UNDER request-axis sharding: an
    8-device mesh engine with the same RetirePolicy retires the same rows at
    the same steps with bitwise-identical samples and conserved saved-NFE
    accounting (the per-row Linf estimate shards over the request axis and
    is reduction-order independent)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _CHILD_FUZZ_EE % ()],
                         capture_output=True, text=True, timeout=1800,
                         env=env)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    assert "FUZZ_MESH_EE_OK" in out.stdout, out.stdout


@pytest.mark.slow  # compiles sharded executors for several batch buckets
def test_fuzz_traffic_sharded_8dev_bitwise():
    """The fuzz invariants hold UNDER request-axis sharding: a forced
    8-device host mesh serves the same randomized workload bit-identically
    to the single-device engine, with structural/join filler excluded from
    waste and zero warm recompiles on replay."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _CHILD_FUZZ % ()],
                         capture_output=True, text=True, timeout=1800,
                         env=env)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    assert "FUZZ_MESH_OK" in out.stdout, out.stdout
