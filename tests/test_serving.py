"""The DEIS diffusion service's streaming continuous-batching semantics:
per-request reproducibility, step-boundary admission, compile/solve time
split, NFE budget accounting, per-step callbacks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.diffusion import lm as DLM
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine, Request


def test_diffusion_engine_batches_same_shape_requests():
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = DiffusionServeEngine(params, cfg)
    reqs = [Request(uid=i, seq_len=16, nfe=4, solver="tab1", seed=0)
            for i in range(3)] + [Request(uid=9, seq_len=24, nfe=4,
                                          solver="tab1", seed=0)]
    res = eng.serve(reqs)
    assert len(res) == 4
    assert all(r.latency_s >= 0.0 and r.compile_s >= 0.0 for r in res)
    by_uid = {r.uid: r for r in res}
    assert by_uid[0].tokens.shape == (16,)
    assert by_uid[9].tokens.shape == (24,)
    # same-group requests were one batched solve -> identical latency records
    assert by_uid[0].latency_s == by_uid[1].latency_s == by_uid[2].latency_s
    # deterministic given seed: same compiled fn, same key
    res2 = eng.serve(reqs)
    np.testing.assert_array_equal(res2[0].tokens, res[0].tokens)


def test_diffusion_engine_nfe_accounting():
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = DiffusionServeEngine(params, cfg)
    res = eng.serve([Request(uid=0, seq_len=8, nfe=6, solver="ddim")])
    assert res[0].nfe == 6


def test_diffusion_engine_shares_executor_across_solver_names():
    """Mixed-solver request groups: the compile cache is keyed on
    (plan signature, batch, seq_len), so solver names whose plans share a
    signature reuse ONE jitted executor instead of one per solver name."""
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = DiffusionServeEngine(params, cfg)

    # 3 solver names, same plan signature (affine/"ab", C shape (N,1)), same
    # (nfe, batch, seq_len) -> 3 groups, 1 executor
    reqs = []
    for j, solver in enumerate(["ddim", "euler", "naive_ei"]):
        reqs += [Request(uid=10 * j + i, seq_len=16, nfe=4, solver=solver,
                         seed=0) for i in range(2)]
    res = eng.serve(reqs)
    assert len(res) == 6
    assert len(eng._plans) == 3
    assert len(eng._compiled) == 1

    # different coefficient shape (tab2: C is (N,3)) -> one more executor
    eng.serve([Request(uid=90 + i, seq_len=16, nfe=4, solver="tab2", seed=0)
               for i in range(2)])
    assert len(eng._compiled) == 2

    # stochastic pair (em / ddim_eta) shares one stochastic-affine executor
    eng.serve([Request(uid=100 + i, seq_len=16, nfe=4, solver="em", seed=0)
               for i in range(2)])
    eng.serve([Request(uid=110 + i, seq_len=16, nfe=4, solver="ddim_eta",
                       eta=1.0, seed=0) for i in range(2)])
    assert len(eng._compiled) == 3

    # results differ across solvers (shared executor, different plan data)
    by_uid = {r.uid: r for r in res}
    assert by_uid[0].tokens.shape == (16,)

    # the explicit-eta contract reaches the serving layer too
    with pytest.raises(ValueError, match="eta"):
        eng.serve([Request(uid=120, seq_len=16, nfe=4, solver="ddim_eta")])


# ------------------------------------------------ streaming engine contracts
@pytest.fixture(scope="module")
def diff_setup():
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def test_streaming_interleaved_groups_match_one_shot(diff_setup):
    """Two groups admitted at different step boundaries, steps interleaved,
    must produce per-request outputs identical to one-shot solves -- both the
    engine's own solo serve and the pure ``sample_tokens_stream`` reference.
    Covers stochastic plans (em, ddim_eta) with distinct per-request seeds."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    # group A: deterministic multistep, two distinct seeds
    eng.submit(Request(uid=0, seq_len=16, nfe=6, solver="tab2", seed=3))
    eng.submit(Request(uid=1, seq_len=16, nfe=6, solver="tab2", seed=4))
    out = eng.tick() + eng.tick()        # A is 2 steps in ...
    # ... when group B (stochastic, mixed names: em + ddim_eta stack) arrives
    eng.submit(Request(uid=2, seq_len=16, nfe=6, solver="em", seed=5))
    eng.submit(Request(uid=3, seq_len=16, nfe=6, solver="ddim_eta", eta=1.0,
                       seed=6))
    while eng.busy:
        out += eng.tick()
    got = {r.uid: r.tokens for r in out}
    assert len(got) == 4

    # one-shot reference 1: the same engine serving each request alone
    solo_eng = DiffusionServeEngine(params, cfg)
    spec = {0: ("tab2", 3, None), 1: ("tab2", 4, None), 2: ("em", 5, None),
            3: ("ddim_eta", 6, 1.0)}
    for uid, (solver, seed, eta) in spec.items():
        solo = solo_eng.serve([Request(uid=uid, seq_len=16, nfe=6,
                                       solver=solver, seed=seed, eta=eta)])
        np.testing.assert_array_equal(solo[0].tokens, got[uid])

    # one-shot reference 2: the pure per-request-keyed sample() path
    from repro.core.plan import stack_plans
    sde = eng.sde
    for uid, (solver, seed, eta) in spec.items():
        plan = eng._plan(solver, 6, eta)
        toks, _ = DLM.sample_tokens_stream(
            params, cfg, stack_plans([plan]), DLM.request_keys([seed]),
            seq_len=16, prior_std=sde.prior_std())
        np.testing.assert_array_equal(np.asarray(toks)[0], got[uid])


def test_per_request_seeds_honored(diff_setup):
    """Distinct seeds in one batched group => distinct samples; equal seeds
    => identical samples, reproducible across serve calls (the old engine
    keyed the whole group on reqs[0].seed)."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    reqs = [Request(uid=i, seq_len=16, nfe=4, solver="ddim_eta", eta=1.0,
                    seed=s) for i, s in enumerate([7, 8, 7])]
    by = {r.uid: r.tokens for r in eng.serve(reqs)}
    np.testing.assert_array_equal(by[0], by[2])      # same seed, same sample
    assert not np.array_equal(by[0], by[1])          # distinct seed differs
    by2 = {r.uid: r.tokens for r in eng.serve(reqs)}  # reproducible
    for uid in by:
        np.testing.assert_array_equal(by[uid], by2[uid])


def test_rk_nfe_budget_honored(diff_setup):
    """RK-family requests must not blow their NFE budget: a nfe=10 rho_rk4
    request runs a 2-interval grid (8 evals), not a 10-interval one (40)."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    res = eng.serve([Request(uid=0, seq_len=8, nfe=10, solver="rho_rk4",
                             seed=0)])
    assert res[0].nfe == 8 and res[0].nfe <= 10
    res = eng.serve([Request(uid=1, seq_len=8, nfe=6, solver="rho_heun",
                             seed=0)])
    assert res[0].nfe == 6
    # pndm's 3x3 extra warmup evals count against the budget too
    res = eng.serve([Request(uid=2, seq_len=8, nfe=20, solver="pndm",
                             seed=0)])
    assert res[0].nfe == 20


def test_latency_excludes_compile(diff_setup):
    """First serve on a cold cache reports compile_s > 0 separately from
    latency_s; a warm-cache serve reports compile_s == 0 (the old engine
    folded trace cost into every request's latency)."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    req = [Request(uid=0, seq_len=12, nfe=3, solver="tab1", seed=0)]
    cold = eng.serve(req)[0]
    assert cold.compile_s > 0 and cold.latency_s > 0
    warm = eng.serve(req)[0]
    assert warm.compile_s == 0.0 and warm.latency_s > 0
    # compile dominates trace-heavy first calls; solve time must not include it
    assert warm.latency_s < cold.latency_s + cold.compile_s


def test_on_step_callback_streams_progress(diff_setup):
    """on_step fires once per group per solver step with progress counters;
    stream_decode=True additionally carries per-step partial decodes of the
    stacked group."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    events = []
    reqs = [Request(uid=i, seq_len=8, nfe=4, solver="ddim", seed=i)
            for i in range(2)]
    res = eng.serve(reqs, on_step=events.append, stream_decode=True)
    assert [e.k for e in events] == [1, 2, 3, 4]
    assert all(e.uids == (0, 1) and e.n_steps == 4 for e in events)
    assert all(e.tokens.shape == (2, 8) for e in events)
    # the last streamed partial decode IS the final result
    final = {r.uid: r.tokens for r in res}
    np.testing.assert_array_equal(events[-1].tokens[0], final[0])
    np.testing.assert_array_equal(events[-1].tokens[1], final[1])


def test_invalid_request_cannot_strand_queued_work(diff_setup):
    """Validation happens at submit time and serve() is all-or-nothing: a bad
    request in a batch leaves the queue empty, and a later serve call sees
    only its own requests."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    good = Request(uid=0, seq_len=8, nfe=3, solver="ddim", seed=0)
    with pytest.raises(ValueError, match="eta"):
        eng.serve([good, Request(uid=1, seq_len=8, nfe=3, solver="ddim_eta")])
    assert not eng.busy                       # uid=0 was rolled back, not lost
    with pytest.raises(ValueError, match="unknown solver"):
        eng.submit(Request(uid=2, seq_len=8, nfe=3, solver="nope"))
    res = eng.serve([Request(uid=3, seq_len=8, nfe=3, solver="ddim", seed=0)])
    assert [r.uid for r in res] == [3]        # no stale strays drained in


# ---------------------------------------- ragged groups / compaction / EDF
def _ragged_reqs():
    """One family bucket (ddim/euler, C width 1) with three NFE budgets."""
    return [Request(uid=0, seq_len=16, nfe=3, solver="ddim", seed=1),
            Request(uid=1, seq_len=16, nfe=6, solver="ddim", seed=2),
            Request(uid=2, seq_len=16, nfe=6, solver="euler", seed=3),
            Request(uid=3, seq_len=16, nfe=9, solver="ddim", seed=4)]


def test_ragged_compaction_bitwise_vs_solo(diff_setup):
    """A ragged-NFE group with compaction produces bitwise-identical samples
    per request vs. solo solves: padding leaves each row's true steps
    untouched, and compaction row-gathers coefficients, state and key chains
    whole. The shrinking batches land in the shared executor cache."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    res = {r.uid: r for r in eng.serve(_ragged_reqs())}
    assert len(res) == 4
    assert eng.wasted_row_steps == 0           # compaction: no dead-row steps
    # one ragged group of 4 compacted to 3 (after nfe=3 retires) then 1
    assert sorted(k[1] for k in eng._compiled) == [1, 3, 4]
    # true per-request NFE survives padding (group plan was padded to 9)
    assert {u: r.nfe for u, r in res.items()} == {0: 3, 1: 6, 2: 6, 3: 9}
    # ragged rows finish EARLY: the nfe=3 row's Result is emitted mid-group
    assert res[0].latency_s < res[3].latency_s
    solo = DiffusionServeEngine(params, cfg)
    for q in _ragged_reqs():
        s = solo.serve([q])[0]
        np.testing.assert_array_equal(s.tokens, res[q.uid].tokens)


def test_compaction_reduces_wasted_row_steps(diff_setup):
    """A ragged group compacts as its short rows retire, so no retired row
    ever steps (a group that kept them would burn 6 + 3 + 3 = 12 dead row
    steps here), and every sample is bitwise the request's solo serve."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    res = {r.uid: r.tokens for r in eng.serve(_ragged_reqs())}
    assert eng.metrics.get("serve_compactions_total").value >= 1
    assert eng.wasted_row_steps == 0
    solo = DiffusionServeEngine(params, cfg)
    for q in _ragged_reqs():
        np.testing.assert_array_equal(solo.serve([q])[0].tokens,
                                      res[q.uid])


def test_deadline_request_preempts_older_work(diff_setup):
    """EDF under a throttled scheduler (steps_per_tick=1): a deadline-tight
    request submitted AFTER an in-flight best-effort group is stepped ahead
    of it every tick until it completes -- and the old work still drains.
    At seq 32, B is in another bucket than A and forms a group of its own;
    at seq 16 (same bucket and priority) B rides in A's group, which its
    deadline then leads."""
    params, cfg = diff_setup
    for join in (False, True):
        eng = DiffusionServeEngine(params, cfg, steps_per_tick=1,
                                   aging_ticks=1000)
        events = []
        eng.submit(Request(uid=0, seq_len=16, nfe=6, solver="tab1", seed=0))
        done = eng.tick(on_step=events.append)          # A in flight, k=1
        eng.submit(Request(uid=1, seq_len=16 if join else 32, nfe=3,
                           solver="tab1", seed=1, deadline_s=0.05))
        while eng.busy:
            done += eng.tick(on_step=events.append)
        if join:
            # one group: B steps every tick from admission, A with it
            assert [e.uids for e in events] == [(0,)] + [(0, 1)] * 3 \
                + [(0,)] * 2
            assert eng.joined_requests == 1
        else:
            # B (deadline) takes every tick from admission until it finishes
            assert [e.uids[0] for e in events] == [0, 1, 1, 1, 0, 0, 0, 0, 0]
        assert [r.uid for r in done] == [1, 0]      # B finishes first


def test_compaction_recomputes_group_urgency(diff_setup):
    """When the urgent row of a ragged group retires, the surviving
    best-effort rows must NOT inherit its priority/deadline: a mid-priority
    newcomer preempts the compacted leftovers (no priority inversion).
    The newcomer's priority (1) differs from the leftover's (0), so it
    forms a group of its own instead of joining the leftover's."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, steps_per_tick=1,
                               aging_ticks=1000)
    eng.submit(Request(uid=0, seq_len=16, nfe=3, solver="ddim", seed=0,
                       priority=2, deadline_s=0.05))
    eng.submit(Request(uid=1, seq_len=16, nfe=9, solver="ddim", seed=1))
    events, done = [], []
    for _ in range(3):                    # urgent row finishes and retires
        done += eng.tick(on_step=events.append)
    assert [r.uid for r in done] == [0]
    eng.submit(Request(uid=2, seq_len=16, nfe=3, solver="ddim", seed=2,
                       priority=1))
    while eng.busy:
        done += eng.tick(on_step=events.append)
    # the newcomer ran ahead of the leftover best-effort row every tick
    assert [e.uids for e in events[3:6]] == [(2,), (2,), (2,)]
    assert [r.uid for r in done] == [0, 2, 1]


@pytest.mark.parametrize("switch", ["compaction", "join", "fused"])
def test_engine_takes_no_mode_switch(switch):
    """Joins, compaction and the fused AB kernel are how the engine always
    serves: it takes no switch to turn one off."""
    with pytest.raises(TypeError):
        DiffusionServeEngine(None, None, **{switch: False})


def test_engine_rejects_invalid_shapes_at_submit(diff_setup):
    """seq_len/nfe validation happens at submit, before anything can reach a
    scheduler tick (a negative seq_len used to blow up inside tick() -- fatal
    for a driver thread)."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    with pytest.raises(ValueError, match="seq_len"):
        eng.submit(Request(uid=0, seq_len=-1, nfe=3, solver="ddim"))
    with pytest.raises(ValueError, match="nfe"):
        eng.submit(Request(uid=0, seq_len=8, nfe=0, solver="ddim"))
    assert not eng.busy


def test_starvation_aging_boosts_skipped_group(diff_setup):
    """A best-effort group facing persistent higher-priority work is boosted
    one effective-priority level per aging_ticks skipped ticks, so it makes
    progress BEFORE the high-priority stream drains (no starvation)."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, steps_per_tick=1, aging_ticks=2)
    events = []
    eng.submit(Request(uid=0, seq_len=16, nfe=4, solver="tab1", seed=0))
    done = eng.tick(on_step=events.append)          # A steps once
    eng.submit(Request(uid=1, seq_len=16, nfe=8, solver="tab1", seed=1,
                       priority=2))
    while eng.busy:
        done += eng.tick(on_step=events.append)
    order = [e.uids[0] for e in events]
    b_span = (order.index(1), len(order) - 1 - order[::-1].index(1))
    # aging got A at least one step strictly inside B's run ...
    assert 0 in order[b_span[0]:b_span[1]], order
    # ... while B (higher priority) still finished first
    assert [r.uid for r in done] == [1, 0]


# ----------------------------------------- continuous admission (joins)
def test_join_at_compaction_boundary_bitwise_vs_solo(diff_setup):
    """A request pending when a group's row retires is spliced INTO the
    surviving group (continuous admission) instead of forming a fresh one,
    and every sample -- veteran and joiner -- is bitwise-identical to its
    solo serve. The joiner's steps count from its own admission tick: its
    nfe is its own plan's, and its latency excludes the group's pre-join
    solve time."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    eng.submit(Request(uid=0, seq_len=16, nfe=3, solver="ddim", seed=1))
    eng.submit(Request(uid=1, seq_len=16, nfe=9, solver="ddim", seed=2))
    out = []
    for _ in range(3):
        out += eng.tick()                    # uid=0 retires at tick 3
    eng.submit(Request(uid=2, seq_len=16, nfe=4, solver="euler", seed=3))
    ticks_before = eng.ticks
    while eng.busy:
        out += eng.tick()
    got = {r.uid: r for r in out}
    assert eng.joined_requests == 1          # uid=2 joined, no fresh group
    assert eng.wasted_row_steps == 0
    # joiner accounting runs on ITS OWN steps, not the group's age
    assert got[2].nfe == 4
    assert got[2].latency_s < got[1].latency_s   # 4 post-join steps < 9
    assert got[2].queue_wait_s >= 0.0
    # the joiner finished 4 ticks after admission (k0=3 -> done at g.k=7)
    assert eng.ticks - ticks_before == 6     # group drains at uid1's k=9
    solo = DiffusionServeEngine(params, cfg)
    for q in [Request(uid=0, seq_len=16, nfe=3, solver="ddim", seed=1),
              Request(uid=1, seq_len=16, nfe=9, solver="ddim", seed=2),
              Request(uid=2, seq_len=16, nfe=4, solver="euler", seed=3)]:
        np.testing.assert_array_equal(solo.serve([q])[0].tokens,
                                      got[q.uid].tokens)


def test_join_keeps_executor_set_fixed(diff_setup):
    """The never-drain/never-recompile contract: replaying the same
    join-heavy workload on a warm engine adds no executors and charges no
    compile time."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)

    def run():
        eng.submit(Request(uid=0, seq_len=16, nfe=3, solver="ddim", seed=1))
        eng.submit(Request(uid=1, seq_len=16, nfe=8, solver="ddim", seed=2))
        out = []
        for _ in range(3):
            out += eng.tick()
        eng.submit(Request(uid=2, seq_len=16, nfe=5, solver="ddim", seed=3))
        while eng.busy:
            out += eng.tick()
        return out

    run()
    n = eng.num_executors
    warm = run()
    assert eng.num_executors == n
    assert all(r.compile_s == 0.0 for r in warm)


def test_join_respects_max_group(diff_setup):
    """Joins never grow a group past max_group: surplus candidates form a
    fresh group under the same urgency order."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, max_group=2)
    eng.submit(Request(uid=0, seq_len=16, nfe=3, solver="ddim", seed=1))
    eng.submit(Request(uid=1, seq_len=16, nfe=6, solver="ddim", seed=2))
    out = []
    for _ in range(3):
        out += eng.tick()                    # uid=0 retired: one free slot
    eng.submit(Request(uid=2, seq_len=16, nfe=4, solver="ddim", seed=3))
    eng.submit(Request(uid=3, seq_len=16, nfe=4, solver="ddim", seed=4))
    while eng.busy:
        out += eng.tick()
    assert eng.joined_requests == 1          # one slot -> one joiner
    assert len(out) == 4
    solo = DiffusionServeEngine(params, cfg)
    for q in [Request(uid=2, seq_len=16, nfe=4, solver="ddim", seed=3),
              Request(uid=3, seq_len=16, nfe=4, solver="ddim", seed=4)]:
        np.testing.assert_array_equal(
            solo.serve([q])[0].tokens,
            {r.uid: r for r in out}[q.uid].tokens)


def test_joiner_longer_than_horizon_forms_fresh_group(diff_setup):
    """A pending request whose grid exceeds the group's horizon cannot join
    (extending the grid would change the signature); it forms a fresh group
    and still solves correctly."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    eng.submit(Request(uid=0, seq_len=16, nfe=3, solver="ddim", seed=1))
    eng.submit(Request(uid=1, seq_len=16, nfe=6, solver="ddim", seed=2))
    out = []
    for _ in range(3):
        out += eng.tick()
    eng.submit(Request(uid=2, seq_len=16, nfe=9, solver="ddim", seed=3))
    while eng.busy:
        out += eng.tick()
    assert eng.joined_requests == 0
    solo = DiffusionServeEngine(params, cfg)
    np.testing.assert_array_equal(
        solo.serve([Request(uid=2, seq_len=16, nfe=9, solver="ddim",
                            seed=3)])[0].tokens,
        {r.uid: r for r in out}[2].tokens)


def test_joined_request_streams_own_progress(diff_setup):
    """StepEvent.row_k counts a joiner's steps from ITS admission tick, so
    per-request progress streams correctly for rows joined mid-flight."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    events = []
    eng.submit(Request(uid=0, seq_len=16, nfe=3, solver="ddim", seed=1))
    eng.submit(Request(uid=1, seq_len=16, nfe=7, solver="ddim", seed=2))
    for _ in range(3):
        eng.tick(on_step=events.append)
    eng.submit(Request(uid=2, seq_len=16, nfe=4, solver="ddim", seed=3))
    while eng.busy:
        eng.tick(on_step=events.append)
    assert eng.joined_requests == 1
    prog = [dict(zip(e.uids, e.row_k)) for e in events]
    assert [p.get(2) for p in prog] == [None, None, None, 1, 2, 3, 4]
    assert [p[1] for p in prog] == [1, 2, 3, 4, 5, 6, 7]   # veteran unmoved


@pytest.mark.parametrize("uid,seq_len,priority,joins", [
    (1, 12, 0, True),        # same bucket (16) and priority: shares the tile
    (2, 8, 0, False),        # the other bucket: a group of its own
    (3, 16, 1, False),       # another priority: a group of its own
], ids=["same_bucket", "other_bucket", "other_priority"])
def test_inflight_join_by_bucket_and_priority(diff_setup, uid, seq_len,
                                              priority, joins):
    """A request that arrives while a lone request of its bucket is in
    flight -- no row retired -- joins that group at the next step boundary;
    one of the other bucket or another priority forms its own. Either way
    both samples equal their solo solves bitwise."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(8, 16))
    first = Request(uid=0, seq_len=16, nfe=4, solver="tab2", seed=1)
    late = Request(uid=uid, seq_len=seq_len, nfe=4, solver="tab2", seed=2,
                   priority=priority)
    eng.submit(first)
    out = eng.tick() + eng.tick()            # the lone request is 2 steps in
    eng.submit(late)
    out += eng.tick()
    assert eng.joined_requests == int(joins)
    assert len(eng._active) == (1 if joins else 2)
    while eng.busy:
        out += eng.tick()
    got = {r.uid: r for r in out}
    assert got[uid].nfe == 4
    solo = DiffusionServeEngine(params, cfg, seq_len_buckets=(8, 16))
    for q in (first, late):
        np.testing.assert_array_equal(solo.serve([q])[0].tokens,
                                      got[q.uid].tokens)


@pytest.mark.parametrize("seq_len,n_first,first_due,late_due,n_late,joined", [
    (128, 1, 60.0, None, 1, 1),  # a lone deadline row: the joiner fills its tile
    (128, 2, 60.0, None, 1, 0),  # the tile is full: a third row would add one
    (128, 2, None, 60.0, 1, 0),  # nor may a deadline joiner open one
    (128, 2, None, None, 1, 1),  # best-effort rows: the joiner opens a tile
    (32, 3, 60.0, None, 6, 5),   # an 8-row tile: 3 deadline rows take 5
    (32, 8, 60.0, None, 1, 0),   # the 8-row tile is full
    (32, 3, None, None, 6, 6),   # best-effort rows take all 6
], ids=["deadline_fills_tile", "deadline_full_tile", "deadline_joiner",
        "best_effort", "deadline_fills_tile8", "deadline_full_tile8",
        "best_effort_tile8"])
def test_join_adds_no_tile_to_a_deadline_row(diff_setup, seq_len, n_first,
                                             first_due, late_due, n_late,
                                             joined):
    """Under ``enforce_deadlines`` a join never makes a deadline row's steps
    run one row tile more: where the group or the joiner carries a
    deadline, a joiner only fills a free slot of the live rows' last tile
    (2 rows wide at seq 128, 8 at seq 32), else it forms its own group. No
    deadline is missed either way."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, enforce_deadlines=True,
                               max_group=16)
    for j in range(n_first):
        eng.submit(Request(uid=j, seq_len=seq_len, nfe=4, solver="tab2",
                           seed=j, deadline_s=first_due))
    out = eng.tick()
    late = list(range(100, 100 + n_late))
    for uid in late:
        eng.submit(Request(uid=uid, seq_len=seq_len, nfe=4, solver="tab2",
                           seed=uid, deadline_s=late_due))
    out += eng.tick()
    assert eng.joined_requests == joined
    assert len(eng._active) == (1 if joined == n_late else 2)
    while eng.busy:
        out += eng.tick()
    assert sorted(r.uid for r in out) == list(range(n_first)) + late
    assert not any(r.deadline_exceeded for r in out)


@pytest.mark.parametrize("solver,seq_len,rows,tiles_per_step", [
    ("tab2", 32, 3, 1),       # one 8-row tile holds the group
    ("tab2", 128, 3, 2),      # 2-row tiles: one full, one part-filled
    ("rho_heun", 32, 3, 2),   # two eps calls per step
])
def test_eps_tile_counters(diff_setup, solver, seq_len, rows, tiles_per_step):
    """``serve_eps_tiles_total`` counts the tile forwards a group step runs
    (the tile loop's trips times the step's eps calls) and
    ``serve_eps_tile_rows_total`` the row slots they offer."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    out = eng.serve([Request(uid=j, seq_len=seq_len, nfe=4, solver=solver,
                             seed=j) for j in range(rows)])
    steps = eng.ticks
    assert len(out) == rows and steps > 0
    tiles = eng.metrics.counter("serve_eps_tiles_total").value
    slots = eng.metrics.counter("serve_eps_tile_rows_total").value
    assert tiles == steps * tiles_per_step
    assert slots == tiles * DLM.tile_rows(seq_len)


def test_warm_engine_joins_without_compiling(diff_setup):
    """Warmed the way the chip benchmark warms a cell -- per bucket, groups
    of 1..max_group admitted, stepped once and dropped, then the decode of
    every finished-row count -- an engine replays in-flight joins,
    compactions and partial finishes without compiling a program: every row
    move of the boundary pass was compiled with the executor it ends at.
    Buckets 10/20 are this test's own, so no other test's programs warm
    them."""
    from jax import monitoring

    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, max_group=3,
                               seq_len_buckets=(10, 20))
    lens = {10: [7, 10], 20: [13, 20]}
    for s_len, ls in lens.items():
        for r in range(1, eng.max_group + 1):
            for j in range(r):
                eng.submit(Request(uid=-1 - j, seq_len=ls[j % 2], nfe=4,
                                   solver="tab2", seed=j))
            eng.tick()
            eng.reset()
        for r in range(1, eng.max_group + 1):      # rows all at the edge
            for j in range(r):
                eng.submit(Request(uid=-1 - j, seq_len=s_len, nfe=4,
                                   solver="tab2", seed=j))
            eng.tick()
            eng.reset()
        x = jnp.zeros((eng.max_group, s_len, cfg.d_model), jnp.float32)
        for r in range(1, eng.max_group + 1):
            np.asarray(DLM.decode_tokens(
                params, cfg, x[:r][jnp.asarray(list(range(r)))]))

    # (arrival tick, seq_len): joins into groups with no retired row, a join
    # into a group with one, compactions, and rows finishing apart
    arrivals = [(0, 20), (1, 13), (2, 20), (2, 7), (3, 10), (4, 13),
                (4, 20), (6, 10), (9, 13)]
    compiled = []

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    monitoring.register_event_duration_secs_listener(on_compile)
    try:
        out, t, i = [], 0, 0
        while i < len(arrivals) or eng.busy:
            while i < len(arrivals) and arrivals[i][0] <= t:
                eng.submit(Request(uid=i, seq_len=arrivals[i][1], nfe=4,
                                   solver="tab2", seed=10 + i))
                i += 1
            out += eng.tick()
            t += 1
    finally:
        monitoring.unregister_event_duration_listener(on_compile)
    assert compiled == []
    assert len(out) == len(arrivals)
    assert eng.joined_requests >= 4
    assert eng.metrics.counter("serve_compactions_total").value >= 1
    solo = DiffusionServeEngine(params, cfg, seq_len_buckets=(10, 20))
    got = {r.uid: r.tokens for r in out}
    for uid in (1, 4, 8):
        q = Request(uid=uid, seq_len=arrivals[uid][1], nfe=4, solver="tab2",
                    seed=10 + uid)
        np.testing.assert_array_equal(solo.serve([q])[0].tokens, got[uid])


def test_seq_len_buckets_share_executor(diff_setup):
    """seq_len_buckets rounds requests up to bucket edges: seq 12 and 16
    solve at one (signature, batch, 16) executor, results are masked back
    to each request's true length, and samples stay reproducible."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(16,))
    reqs = [Request(uid=0, seq_len=12, nfe=4, solver="ddim", seed=1),
            Request(uid=1, seq_len=16, nfe=4, solver="ddim", seed=2)]
    res = {r.uid: r for r in eng.serve(list(reqs))}
    assert res[0].tokens.shape == (12,)
    assert res[1].tokens.shape == (16,)
    # ONE executor: both lengths bucket to 16 and stack into one group
    assert {(k[1], k[2]) for k in eng._compiled} == {(2, 16)}
    # reproducible; solo reference shares the bucket config
    solo = DiffusionServeEngine(params, cfg, seq_len_buckets=(16,))
    for q in reqs:
        np.testing.assert_array_equal(solo.serve([q])[0].tokens,
                                      res[q.uid].tokens)
    # beyond the last edge: exact length, no bucketing
    big = eng.serve([Request(uid=2, seq_len=24, nfe=4, solver="ddim",
                             seed=3)])[0]
    assert big.tokens.shape == (24,)
    with pytest.raises(ValueError, match="seq_len_buckets"):
        DiffusionServeEngine(params, cfg, seq_len_buckets=(16, 8))


def test_seq_len_bucket_content_matches_unbucketed(diff_setup):
    """Bucket-independence for deterministic solvers: the prior is drawn at
    the request's TRUE length and padded tail keys are masked out of every
    attention call, so a seq-12 request solved in a 16-bucket returns the
    SAME tokens as the same request solved unbucketed at its exact length
    (the PR-5 caveat this kills: sample content used to depend on which
    bucket a request landed in)."""
    params, cfg = diff_setup
    req = Request(uid=0, seq_len=12, nfe=4, solver="ddim", seed=9)
    bucketed = DiffusionServeEngine(params, cfg, seq_len_buckets=(16,))
    exact = DiffusionServeEngine(params, cfg)
    got = bucketed.serve([dataclasses.replace(req)])[0]
    want = exact.serve([dataclasses.replace(req)])[0]
    assert got.tokens.shape == want.tokens.shape == (12,)
    np.testing.assert_array_equal(got.tokens, want.tokens)


# ------------------------------------------------ a dropless MoE eps-net
@pytest.fixture(scope="module")
def moe_setup():
    """sdar-30b-a3b at a tiny size as one chip's share: 4 of 8 experts held
    (ids 2-5), top-2, dropless routing through the expert kernel."""
    cfg = get_config("sdar_30b_a3b").reduced().with_(objective="diffusion")
    cfg = cfg.with_(moe=dataclasses.replace(
        cfg.moe, num_experts=8, top_k=2, experts_held=4, expert_offset=2))
    return T.init_params(cfg, jax.random.PRNGKey(0)), cfg


def test_join_at_compaction_boundary_bitwise_vs_solo_moe(moe_setup):
    """The join case on a dropless MoE: the joiner is spliced in, and every
    row decodes bitwise as it does served alone."""
    test_join_at_compaction_boundary_bitwise_vs_solo(moe_setup)


def test_seq_len_bucket_content_matches_unbucketed_moe(moe_setup):
    """Bucket-independence on a dropless MoE: the tail positions share no
    capacity with the valid ones."""
    test_seq_len_bucket_content_matches_unbucketed(moe_setup)


def test_moe_assignments_counted_per_request(moe_setup, diff_setup):
    """``Result.moe_assignments``: the held experts' token-expert pairs at
    the request's own positions over every layer and NFE, the same stacked
    as solo, and summed in ``serve_moe_assignments_total``; a pndm plan,
    whose evals run under a cond, reports None."""
    params, cfg = moe_setup
    reqs = [Request(uid=i, seq_len=n, nfe=4, solver="tab3", seed=i)
            for i, n in enumerate([16, 11, 6])]
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(16,))
    got = {r.uid: r for r in eng.serve(reqs)}
    solo = DiffusionServeEngine(params, cfg, seq_len_buckets=(16,))
    for q in reqs:
        n = got[q.uid].moe_assignments
        assert n == solo.serve([dataclasses.replace(q)])[0].moe_assignments
        assert 0 < n < q.seq_len * cfg.n_layers * got[q.uid].nfe * 2
    assert eng.metrics.get("serve_moe_assignments_total").value \
        == sum(r.moe_assignments for r in got.values())
    pndm = eng.serve([Request(uid=9, seq_len=16, nfe=13, solver="pndm")])
    assert pndm[0].moe_assignments is None
    dense, dcfg = diff_setup
    assert DiffusionServeEngine(dense, dcfg).serve(
        [Request(uid=0, seq_len=8, nfe=3)])[0].moe_assignments is None


def test_seq_len_bucket_stream_decode_masks_tail(diff_setup):
    """stream_decode under bucketing: group events carry bucket-length rows
    plus row_seq_lens so consumers (the driver) can mask the tail; final
    Results are already masked."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(16,))
    events = []
    res = eng.serve([Request(uid=0, seq_len=10, nfe=3, solver="ddim",
                             seed=1)],
                    on_step=events.append, stream_decode=True)
    assert all(e.tokens.shape == (1, 16) for e in events)
    assert all(e.row_seq_lens == (10,) for e in events)
    assert res[0].tokens.shape == (10,)
    np.testing.assert_array_equal(events[-1].tokens[0][:10], res[0].tokens)


def test_admission_splits_oversized_buckets(diff_setup):
    """Buckets larger than max_group split into multiple stacked groups, each
    with its own executor cache entry keyed on its batch size."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, max_group=2)
    reqs = [Request(uid=i, seq_len=8, nfe=3, solver="ddim", seed=i)
            for i in range(5)]
    res = eng.serve(reqs)
    assert len(res) == 5
    assert {k[1] for k in eng._compiled} == {2, 1}   # two of 2, one of 1
