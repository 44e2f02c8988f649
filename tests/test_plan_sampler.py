"""Plan/step sampler API: deprecated-factory <-> SolverPlan equivalence for
every solver name, step-wise resume, hooks, jit/vmap composition, and the
explicit-eta factory contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (VPSDE, Hooks, SOLVER_NAMES, get_timesteps, init_state,
                        make_plan, make_solver, plan_ddim, sample, stack_plans,
                        step)
from repro.diffusion.analytic import GaussianData

SDE = VPSDE()
TS = get_timesteps(SDE, 8, "quadratic")
KEY = jax.random.PRNGKey(7)


def _problem(d=4, batch=8):
    g = GaussianData(SDE, mean=np.full(d, 1.5), var=np.full(d, 0.25))
    xT = jax.random.normal(jax.random.PRNGKey(0), (batch, d)) * SDE.prior_std()
    return g.eps_fn(), xT


def _kw(name):
    return {"eta": 1.0} if name == "ddim_eta" else {}


# --------------------------------------------- deprecated factory equivalence
@pytest.mark.parametrize("name", SOLVER_NAMES)
def test_deprecated_make_solver_aliases_make_plan(name):
    """The class shims are gone: ``make_solver`` warns and returns exactly
    the plan ``make_plan`` builds, for every solver name (so stragglers keep
    working, one DeprecationWarning louder)."""
    with pytest.deprecated_call():
        legacy = make_solver(name, SDE, TS, **_kw(name))
    plan = make_plan(name, SDE, TS, **_kw(name))
    assert legacy.signature == plan.signature and legacy.nfe == plan.nfe
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), legacy, plan)
    eps, xT = _problem()
    np.testing.assert_array_equal(
        np.asarray(sample(legacy, eps, xT, KEY)),
        np.asarray(sample(plan, eps, xT, KEY)))


def test_plan_matches_hand_rolled_ddim_eta():
    """Golden pre-redesign formula (Eq. 34): x' = a x + b eps + s xi with the
    per-step key-split pattern -- guards the redesign against drift."""
    eps, xT = _problem()
    eta = 1.0
    ab = np.asarray(SDE.alpha_bar(TS), dtype=np.float64)
    sig2 = (eta ** 2) * (1 - ab[1:]) / (1 - ab[:-1]) * (1 - ab[:-1] / ab[1:])
    sig2 = np.maximum(sig2, 0.0)
    a = np.sqrt(ab[1:] / ab[:-1])
    b = np.sqrt(np.maximum(1 - ab[1:] - sig2, 0.0)) - a * np.sqrt(1 - ab[:-1])
    s = np.sqrt(sig2)
    x, key = xT, KEY
    for k in range(len(TS) - 1):
        key, sub = jax.random.split(key)
        e = eps(x, jnp.asarray(TS[k], x.dtype))
        xi = jax.random.normal(sub, x.shape, x.dtype)
        x = a[k] * x + b[k] * e + s[k] * xi
    got = sample(plan_ddim(SDE, TS, eta=eta), eps, xT, KEY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), rtol=1e-10,
                               atol=1e-10)


def test_plan_matches_hand_rolled_euler():
    """Golden pre-redesign Euler loop: x += dt (f x + g^2/(2 sigma) eps)."""
    eps, xT = _problem()
    f = np.asarray(SDE.f(TS[:-1]), dtype=np.float64)
    coef = 0.5 * np.asarray(SDE.g2(TS[:-1]), np.float64) \
        / np.asarray(SDE.sigma(TS[:-1]), np.float64)
    dt = TS[1:] - TS[:-1]
    x = xT
    for k in range(len(TS) - 1):
        e = eps(x, jnp.asarray(TS[k], x.dtype))
        x = x + dt[k] * (f[k] * x + coef[k] * e)
    got = sample(make_plan("euler", SDE, TS), eps, xT)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), rtol=1e-10,
                               atol=1e-10)


# ----------------------------------------------------------- step / resume
@pytest.mark.parametrize("name", ["ddim", "tab3", "rho_heun", "dpm2", "em",
                                  "ddim_eta", "ipndm3", "pndm"])
def test_step_loop_matches_sample(name):
    """sample() == init_state() + step() iterated -- the streaming/resume
    contract serving relies on."""
    eps, xT = _problem()
    plan = make_plan(name, SDE, TS, **_kw(name))
    want = sample(plan, eps, xT, KEY)
    st = init_state(plan, xT, KEY)
    for k in range(plan.n_steps):
        st = step(plan, k, st, eps)
    np.testing.assert_allclose(np.asarray(st.x), np.asarray(want),
                               rtol=1e-10, atol=1e-12)
    assert int(st.k) == plan.n_steps


def test_mid_solve_resume():
    """A solve split across two owners (SamplerState handed over mid-way)
    equals the uninterrupted solve."""
    eps, xT = _problem()
    plan = make_plan("tab2", SDE, TS)
    st = init_state(plan, xT)
    for k in range(plan.n_steps // 2):
        st = step(plan, k, st, eps)
    handoff = jax.tree.map(jnp.array, st)  # serialize/restore stand-in
    for k in range(plan.n_steps // 2, plan.n_steps):
        handoff = step(plan, k, handoff, eps)
    want = sample(plan, eps, xT)
    np.testing.assert_allclose(np.asarray(handoff.x), np.asarray(want),
                               rtol=1e-10, atol=1e-12)


def test_stochastic_plan_requires_key():
    eps, xT = _problem()
    for name in ("em", "ddim_eta"):
        with pytest.raises(ValueError, match="PRNG key"):
            sample(make_plan(name, SDE, TS, **_kw(name)), eps, xT)


# ------------------------------------------------------------------- hooks
def test_trajectory_hook():
    eps, xT = _problem()
    plan = make_plan("tab2", SDE, TS)
    x0, traj = sample(plan, eps, xT, hooks=Hooks(record_trajectory=True))
    assert traj.shape == (plan.n_steps,) + xT.shape
    np.testing.assert_array_equal(np.asarray(traj[-1]), np.asarray(x0))
    np.testing.assert_array_equal(np.asarray(x0),
                                  np.asarray(sample(plan, eps, xT)))


def test_guidance_hook_scales_eps():
    """eps_transform is applied to every network output (identity == no-op;
    a scaling transform must change the result)."""
    eps, xT = _problem()
    plan = make_plan("tab2", SDE, TS)
    base = sample(plan, eps, xT)
    same = sample(plan, eps, xT, hooks=Hooks(eps_transform=lambda x, t, e: e))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(same))
    scaled = sample(plan, eps, xT,
                    hooks=Hooks(eps_transform=lambda x, t, e: 1.5 * e))
    assert not np.allclose(np.asarray(base), np.asarray(scaled))


# ------------------------------------------------------- jit / vmap / cache
def test_jit_shares_executor_across_same_signature_plans():
    """Plans are traced arguments: solver names with equal plan signatures
    (ddim / euler / naive_ei at one NFE) share a single compiled executor."""
    eps, xT = _problem()
    run = jax.jit(lambda p, x: sample(p, eps, x))
    outs = [run(make_plan(n, SDE, TS), xT) for n in ("ddim", "euler", "naive_ei")]
    assert run._cache_size() == 1
    # and they are *different* solvers, not one trace constant-folded
    assert not np.allclose(np.asarray(outs[0]), np.asarray(outs[1]))


def test_vmap_over_batched_state():
    eps, xT = _problem(batch=6)
    plan = make_plan("tab1", SDE, TS)
    got = jax.vmap(lambda x: sample(plan, eps, x))(xT[:, None, :])[:, 0, :]
    want = sample(plan, eps, xT)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-10, atol=1e-12)


# ------------------------------------------------------------- eta contract
def test_make_solver_ddim_eta_requires_explicit_eta():
    """The old factory silently defaulted to eta=1.0 while the class shim
    defaulted to eta=0.0; both factories now require eta explicitly."""
    with pytest.raises(TypeError, match="eta"), pytest.deprecated_call():
        make_solver("ddim_eta", SDE, TS)
    with pytest.raises(TypeError, match="eta"):
        make_plan("ddim_eta", SDE, TS)


def test_ddim_eta_forwarded():
    eps, xT = _problem()
    det = sample(make_plan("ddim_eta", SDE, TS, eta=0.0), eps, xT)
    ddim = sample(make_plan("ddim", SDE, TS), eps, xT)
    np.testing.assert_allclose(np.asarray(det), np.asarray(ddim),
                               rtol=1e-9, atol=1e-9)
    sto = make_plan("ddim_eta", SDE, TS, eta=1.0)
    assert sto.stochastic
    assert not np.allclose(
        np.asarray(sample(sto, eps, xT, KEY)), np.asarray(ddim))


# ------------------------------------------------------------ stacked plans
def _per_request_keys(seeds):
    return jnp.stack([jax.random.PRNGKey(s) for s in seeds])


@pytest.mark.parametrize("names,keys", [
    (("ddim", "euler", "naive_ei"), None),       # mixed deterministic names
    (("tab2", "tab2", "tab2"), None),            # homogeneous multistep
    (("rho_rk4", "rho_rk4", "rho_rk4"), None),   # homogeneous RK
    (("rho_heun", "dpm2", "rho_midpoint"), None),  # mixed RK tableaus
    (("em", "ddim_eta", "em"), (11, 12, 13)),    # mixed stochastic
])
def test_stacked_rows_bitwise_match_single_request_solves(names, keys):
    """Row i of a stacked solve is bit-identical to solving request i alone
    (same key chain, same draws) -- the per-request reproducibility contract
    streamed serving is built on.

    One carve-out: mixed RK tableaus give each row *different* stage times,
    and CPU SIMD transcendentals (exp in sde.mu) may differ by 1 ulp between
    packet lanes and the scalar remainder path depending on vector length.
    That case asserts <= 1 ulp instead of bit equality."""
    eps, xT = _problem(batch=len(names))
    plans = [make_plan(n, SDE, TS, **_kw(n)) for n in names]
    kstack = _per_request_keys(keys) if keys else None
    out = sample(stack_plans(plans), eps, xT, kstack)
    mixed_t_rows = plans[0].method == "rk" and len(
        {np.asarray(p.coeffs["stage_t"]).tobytes() for p in plans}) > 1
    for i, p in enumerate(plans):
        solo = sample(stack_plans([p]), eps, xT[i:i + 1],
                      kstack[i:i + 1] if keys else None)
        if mixed_t_rows:
            np.testing.assert_allclose(np.asarray(solo[0]), np.asarray(out[i]),
                                       rtol=1e-15, atol=0)
        else:
            np.testing.assert_array_equal(np.asarray(solo[0]),
                                          np.asarray(out[i]))


def test_interleaved_stacked_step_groups_match_one_shot_sample():
    """The streaming schedule: two groups admitted at different step
    boundaries, steps interleaved, equals one-shot sample() per request --
    including stochastic plans with distinct per-request seeds."""
    eps, xT = _problem(batch=4)
    ga = stack_plans([make_plan("tab2", SDE, TS)] * 2)            # group A
    gb = stack_plans([make_plan("em", SDE, TS),                    # group B
                      make_plan("ddim_eta", SDE, TS, eta=1.0)])
    kb = _per_request_keys([21, 22])
    sa = init_state(ga, xT[:2])
    for k in range(2):                       # A runs 2 steps before B arrives
        sa = step(ga, k, sa, eps)
    sb = init_state(gb, xT[2:], kb)
    ka = 2
    for k in range(gb.n_steps):              # interleave A and B per tick
        if ka < ga.n_steps:
            sa = step(ga, ka, sa, eps)
            ka += 1
        sb = step(gb, k, sb, eps)
    want_a = sample(ga, eps, xT[:2])
    want_b = sample(gb, eps, xT[2:], kb)
    np.testing.assert_allclose(np.asarray(sa.x), np.asarray(want_a),
                               rtol=1e-12, atol=1e-14)
    np.testing.assert_allclose(np.asarray(sb.x), np.asarray(want_b),
                               rtol=1e-12, atol=1e-14)
    # distinct seeds produced distinct stochastic samples
    assert not np.allclose(np.asarray(sb.x[0]), np.asarray(sb.x[1]))


def test_stacked_step_is_single_trace_over_k():
    """One jitted step serves every step index of a stacked plan (k is a
    traced argument), including pndm's structural warmup/tail split."""
    eps, xT = _problem(batch=2)
    for name in ("tab2", "rho_heun", "pndm"):
        ts = get_timesteps(SDE, 8, "uniform") if name == "pndm" else TS
        plan = stack_plans([make_plan(name, SDE, ts)] * 2)
        run = jax.jit(lambda k, st, p=plan: step(p, k, st, eps))
        st = init_state(plan, xT)
        for k in range(plan.n_steps):
            st = run(jnp.int32(k), st)
        assert run._cache_size() == 1
        np.testing.assert_allclose(np.asarray(st.x),
                                   np.asarray(sample(plan, eps, xT)),
                                   rtol=1e-10, atol=1e-12)


def test_stack_plans_rejects_mismatched_signatures():
    with pytest.raises(ValueError, match="signature"):
        stack_plans([make_plan("ddim", SDE, TS), make_plan("tab2", SDE, TS)])
    with pytest.raises(ValueError, match="stack"):
        stack_plans([stack_plans([make_plan("ddim", SDE, TS)])])


# ---------------------------------------- ragged plans: pad / family / gather
@pytest.mark.parametrize("name", ["ddim", "tab3", "rho_rk4", "pndm", "em"])
def test_pad_plan_prefix_bitwise_and_family(name):
    """Padding preserves the original steps bit-for-bit (the padded solve's
    first n steps equal the unpadded solve), keeps padded steps finite, and
    makes same-family/different-NFE plans stackable. rho_rk4 guards the
    registry: its per-stage ``b`` weights share a length with a 4-step grid
    and must NOT be treated as a step axis."""
    from repro.core import pad_plan
    n1, n2 = (5, 9) if name == "pndm" else (4, 8)
    p1 = make_plan(name, SDE, get_timesteps(SDE, n1, "quadratic"), **_kw(name))
    p2 = make_plan(name, SDE, get_timesteps(SDE, n2, "quadratic"), **_kw(name))
    assert p1.family == p2.family
    assert p1.signature != p2.signature
    padded = pad_plan(p1, p2.n_steps)
    assert padded.signature == p2.signature and padded.nfe == p1.nfe
    eps, xT = _problem(batch=2)
    st_a, st_b = init_state(p1, xT, KEY), init_state(padded, xT, KEY)
    for k in range(p1.n_steps):
        st_a = step(p1, k, st_a, eps)
        st_b = step(padded, k, st_b, eps)
    np.testing.assert_array_equal(np.asarray(st_a.x), np.asarray(st_b.x))
    for k in range(p1.n_steps, padded.n_steps):   # inert region stays finite
        st_b = step(padded, k, st_b, eps)
    assert np.all(np.isfinite(np.asarray(st_b.x)))
    stacked = stack_plans([padded, p2])
    assert stacked.batch == 2 and stacked.nfe == max(p1.nfe, p2.nfe)


def test_take_rows_and_state_rows_bit_exact_mid_solve():
    """Mid-solve compaction primitive: gathering rows of a stacked stochastic
    solve and continuing yields bitwise the same per-request samples as the
    uncompacted stack (key chains move whole)."""
    from repro.core import take_rows, take_state_rows
    eps, _ = _problem(d=4)
    plans = [make_plan("em", SDE, TS)] * 3
    keys = jnp.stack([jax.random.PRNGKey(s) for s in (3, 4, 5)])
    xT = jax.vmap(lambda kk: jax.random.normal(kk, (4,)))(
        jnp.stack([jax.random.PRNGKey(s) for s in (13, 14, 15)]))
    full = stack_plans(plans)
    st_full = init_state(full, xT, keys)
    st_cmp = init_state(full, xT, keys)
    cmp_plan = full
    for k in range(full.n_steps):
        st_full = step(full, k, st_full, eps)
        st_cmp = step(cmp_plan, k, st_cmp, eps)
        if k == 2:                                  # compact away row 1
            cmp_plan = take_rows(cmp_plan, [0, 2])
            st_cmp = take_state_rows(st_cmp, [0, 2])
    np.testing.assert_array_equal(np.asarray(st_full.x)[[0, 2]],
                                  np.asarray(st_cmp.x))
    with pytest.raises(ValueError, match="stacked"):
        take_rows(make_plan("ddim", SDE, TS), [0])
    with pytest.raises(ValueError, match="non-empty"):
        take_state_rows(st_cmp, [])


def test_stacked_state_validation():
    plan = stack_plans([make_plan("em", SDE, TS)] * 2)
    eps, xT = _problem(batch=2)
    with pytest.raises(ValueError, match="PRNG key"):
        init_state(plan, xT)                       # stochastic needs keys
    with pytest.raises(ValueError, match="per-request keys"):
        init_state(plan, xT, jax.random.PRNGKey(0))  # one key is not enough
    with pytest.raises(ValueError, match="leading axis"):
        init_state(plan, xT[:1], _per_request_keys([1, 2]))


@pytest.mark.parametrize("name,ks,evals", [
    ("tab3", [0, 5], 1),
    ("rho_heun", [0, 5], 2),
    ("rho_rk4", [2, 2], 4),
    ("pndm", [0, 2], 4),        # every row in the warmup
    ("pndm", [3, 7], 1),        # every row in the tail
    ("pndm", [1, 4], 5),        # rows on both sides: both branches run
])
def test_step_evals_counts_the_eps_calls_a_step_runs(name, ks, evals):
    """``step_evals`` is the number of eps calls one jitted stacked step
    runs at the per-row step vector ``ks``, counted on the device side
    (a call inside a ``lax.cond`` branch counts only where it runs)."""
    from repro.core.sampler import step_evals
    _, xT = _problem(batch=2)
    ts = get_timesteps(SDE, 8, "uniform") if name == "pndm" else TS
    plan = stack_plans([make_plan(name, SDE, ts)] * 2)
    ran = []

    def eps(x, t):
        jax.debug.callback(lambda: ran.append(1))
        return x * 0.1

    out = jax.jit(lambda k, st: step(plan, k, st, eps))(
        jnp.asarray(ks, jnp.int32), init_state(plan, xT))
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert len(ran) == evals == step_evals(plan, ks)


def test_plan_nfe_accounting():
    assert make_plan("pndm", SDE, get_timesteps(SDE, 20, "uniform")).nfe == 29
    assert make_plan("ipndm3", SDE, TS).nfe == 8
    assert make_plan("rho_heun", SDE, TS).nfe == 16
    assert make_plan("rho_rk4", SDE, TS).nfe == 32


# ------------------------------------- transfer guard: dynamic twin of RL001
# One solver per stepper family (ab/rk/stochastic/pndm): the full solve must
# run without a single implicit device<->host transfer -- the runtime check
# backing the static host-sync lint (see docs/static_analysis.md).
GUARD_NAMES = ["tab3", "rho_heun", "em", "pndm"]


@pytest.fixture(scope="module")
def guard_prep():
    """Everything host-touching happens here, OUTSIDE the guard: plan
    construction (numpy coefficient tables), input materialization, jit
    wrapping, device-resident int32 step indices, and the unguarded
    reference solve. Tests then run only jitted device work under the
    guard and fetch results with an explicit ``jax.device_get``."""
    eps, xT = _problem()
    out = {}
    for name in GUARD_NAMES:
        ts = TS if name != "pndm" else get_timesteps(SDE, 8, "uniform")
        plan = make_plan(name, SDE, ts)
        jit_step = jax.jit(lambda k, st, _p=plan: step(_p, k, st, eps))
        jit_sample = jax.jit(lambda _p=plan: sample(_p, eps, xT, KEY))
        out[name] = {
            "state0": init_state(plan, xT, KEY),
            "jit_step": jit_step,
            "jit_sample": jit_sample,
            "ks": [jnp.int32(k) for k in range(plan.n_steps)],
            "want": np.asarray(sample(plan, eps, xT, KEY)),
        }
    return out


@pytest.mark.parametrize("name", GUARD_NAMES)
def test_sample_no_implicit_transfers(name, guard_prep, no_implicit_transfers):
    """A jitted full solve compiles and runs entirely on-device: any stray
    ``float()``/``bool()``/np coercion in the plan/sampler path would raise
    under the guard (including during the cold compile, which happens
    inside it)."""
    p = guard_prep[name]
    got = jax.device_get(p["jit_sample"]())
    np.testing.assert_allclose(got, p["want"], rtol=1e-7, atol=1e-9)


@pytest.mark.parametrize("name", GUARD_NAMES)
def test_step_loop_no_implicit_transfers(name, guard_prep,
                                         no_implicit_transfers):
    """The serving-style loop -- one jitted ``step`` per k with k as a
    device int32 -- stays transfer-free across every step of every stepper
    family, and lands on the same x_0 as the fused solve."""
    p = guard_prep[name]
    st = p["state0"]
    for k in p["ks"]:
        st = p["jit_step"](k, st)
    got = jax.device_get(st.x)
    np.testing.assert_allclose(got, p["want"], rtol=1e-7, atol=1e-9)
