"""Single pure executor for every :class:`~repro.core.plan.SolverPlan`.

Public API:

  ``sample(plan, eps_fn, x_T, key=None, *, hooks=None)``
      Run the full fixed-step solve (a ``lax.fori_loop`` for ab/rk plans;
      PNDM's warmup is statically unrolled like the original algorithm).
      Returns the final state ``x_0``, or ``(x_0, trajectory)`` when
      ``hooks.record_trajectory`` is set.

  ``step(plan, k, state, eps_fn, *, hooks=None)``
      One solver step as a pure function on an explicit ``SamplerState``.
      This is what serving uses to interleave steps across batches, stream
      per-step progress, and resume mid-solve: ``sample`` is exactly
      ``init_state`` + ``step`` iterated, so splitting a solve across calls
      reproduces the one-shot result (to machine epsilon -- XLA may fuse the
      loop body differently than an eagerly dispatched step). ``k`` may be a
      tracer for every method (pndm's structural warmup/tail split is a
      ``lax.cond`` under a traced ``k``), so one jitted ``step`` serves all
      step indices of a plan. For a *stacked* plan ``k`` may also be a
      per-row ``(R,)`` int vector: row ``i`` advances from its OWN step
      ``k[i]``, which is what lets serving join a fresh request (at its
      k=0) into a group whose veteran rows are mid-solve. A per-row ``k``
      is clamped to the plan's grid, so retired rows riding a group past
      their own horizon index only inert padded steps.

  ``init_state(plan, x_T, key=None)``
      Build the initial ``SamplerState``. Stochastic plans require a PRNG
      key; deterministic plans carry a dummy key untouched.

Stacked plans (:func:`repro.core.plan.stack_plans`) batch *heterogeneous*
requests: coefficient leaves carry a leading request axis ``R``, ``x`` is
``(R, *inner)`` and ``state.key`` is a ``(R, 2)`` stack of per-request PRNG
keys. Row ``i`` of a stacked solve draws exactly the noise a single-request
solve under ``keys[i]`` would draw (vmapped key splits + per-row draws), which
is what makes streamed serving per-request reproducible.

Everything is a pytree in, pytree out -- ``jax.jit``/``vmap``/``pjit``
compose over ``sample`` and ``step`` with the plan as a traced argument, so
one compiled executor serves every plan with the same :attr:`SolverPlan.signature`.
``Hooks`` are pytree-closed callables (guidance transforms close over arrays;
no Python state), keeping the loop traceable.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import fused_ab_step as _fused_ab_step
from .plan import SolverPlan

Array = jax.Array
EpsFn = Callable[[Array, Array], Array]


class SamplerState(NamedTuple):
    """Explicit solver state: everything needed to resume a solve mid-way."""
    x: Array      # current iterate
    hist: Array   # (R, *x.shape) eps history, newest first (R may be 0)
    key: Array    # PRNG key (consumed only by stochastic plans)
    k: Array      # int32 step counter (informational; `step` takes k explicitly)
    err: Array    # running local-error estimate: max-abs (Linf) of the last
    #               step's embedded lower-order difference; (R,) stacked,
    #               scalar unstacked. +inf until the plan produces a first
    #               estimate (plans without `error_estimate`, warmup steps);
    #               steps with zeroed companion weights (inert/padded rows)
    #               leave it unchanged. Linf deliberately: max-reductions are
    #               reduction-order independent, so err is bitwise identical
    #               across batch compositions -- the serving early-exit
    #               invariant (retire at the same k as a solo solve) rests
    #               on this.


@dataclasses.dataclass(frozen=True)
class Hooks:
    """Pytree-closed per-step extension points.

    eps_transform: ``(x, t, eps) -> eps`` applied to every network output
        (guidance, thresholding). Must be traceable; closures over arrays ok.
    record_trajectory: when True, ``sample`` also returns the (n_steps, ...)
        stack of post-step iterates.
    """
    eps_transform: Optional[Callable[[Array, Array, Array], Array]] = None
    record_trajectory: bool = False


_DEFAULT_HOOKS = Hooks()


def init_state(plan: SolverPlan, x_T: Array, key: Optional[Array] = None) -> SamplerState:
    """Build the initial :class:`SamplerState` for ``plan`` at ``x_T``.

    Shape contract: unstacked plans take ``x_T`` of any shape and an optional
    single PRNG key; a stacked plan of ``R`` requests takes ``x_T`` of shape
    ``(R, *inner)`` and per-request keys of shape ``(R, 2)``. ``hist`` is
    allocated as ``(plan.history_len, *x_T.shape)`` zeros. Stochastic plans
    REQUIRE a key (deterministic plans carry a dummy key untouched), which is
    the root of the reproducibility guarantee: every later draw is a pure
    function of this initial key (chain)."""
    if plan.stochastic and key is None:
        raise ValueError(f"stochastic plan (method={plan.method!r}) requires a PRNG key")
    if plan.stacked:
        if x_T.ndim < 1 or x_T.shape[0] != plan.batch:
            raise ValueError(f"stacked plan of {plan.batch} requests needs "
                             f"x_T with leading axis {plan.batch}, got "
                             f"{x_T.shape}")
        if key is None:
            key = jnp.zeros((plan.batch, 2), jnp.uint32)
        if key.ndim != 2 or key.shape[0] != plan.batch:
            raise ValueError(f"stacked plan of {plan.batch} requests needs "
                             f"per-request keys of shape ({plan.batch}, 2), "
                             f"got {key.shape}")
    elif key is None:
        key = jax.random.PRNGKey(0)
    hist = jnp.zeros((plan.history_len,) + x_T.shape, x_T.dtype)
    err = jnp.full(x_T.shape[:1] if plan.stacked else (), jnp.inf, x_T.dtype)
    return SamplerState(x=x_T, hist=hist, key=key, k=jnp.int32(0), err=err)


@jax.jit
def gather_state_rows(state: SamplerState, idx) -> SamplerState:
    """The device half of :func:`take_state_rows`, one program per (rows
    in, rows out); the serving engine compiles it ahead for every pair it
    can meet, as it does :func:`repro.core.plan.gather_plan_rows`."""
    return SamplerState(x=state.x[idx], hist=state.hist[:, idx],
                        key=state.key[idx], k=state.k, err=state.err[idx])


@jax.jit
def concat_state_rows(state: SamplerState, new: SamplerState) -> SamplerState:
    """The device half of :func:`join_state_rows`."""
    return SamplerState(x=jnp.concatenate([state.x, new.x], axis=0),
                        hist=jnp.concatenate([state.hist, new.hist], axis=1),
                        key=jnp.concatenate([state.key, new.key], axis=0),
                        k=state.k,
                        err=jnp.concatenate([state.err, new.err], axis=0))


def take_state_rows(state: SamplerState, rows) -> SamplerState:
    """Row-gather a stacked solve's state: keep requests ``rows``, in order.

    Gathers ``x`` on axis 0, ``hist`` on axis 1 (its layout is
    ``(history_len, R, *inner)``) and the per-request key stack on axis 0;
    the step counter ``k`` is untouched. Because every per-request quantity
    -- including each row's PRNG key chain -- is carried whole, continuing a
    compacted solve is *bit-exact*: surviving row ``i`` takes exactly the
    remaining steps and noise draws it would have taken in the larger stack
    (or solo). This is the state half of mid-flight group compaction; the
    plan half is :func:`repro.core.plan.take_rows`.
    """
    # repro: allow[RL001] rows is a host-side index list by contract (scheduler bookkeeping)
    idx = np.asarray(rows, dtype=np.int32)
    if idx.ndim != 1 or idx.size == 0:
        raise ValueError(f"rows must be a non-empty 1-D index sequence, got "
                         f"shape {idx.shape}")
    return gather_state_rows(state, idx)


def join_state_rows(state: SamplerState, new: SamplerState) -> SamplerState:
    """Splice a fresh stacked state onto an in-flight stacked solve's rows.

    ``new`` is the joiners' own freshly-initialised stacked state (from
    :func:`init_state` at their per-request keys). ``x`` and the key stack
    concatenate on axis 0, ``hist`` on axis 1 (layout ``(history_len, R,
    *inner)``), so the veteran rows' leaves occupy the SAME leading slots
    bit-for-bit -- joining never moves an in-flight request. The joiners
    carry zero eps history and their untouched key chains, exactly what a
    solo solve starts from; stepped with a per-row ``k`` vector (their rows
    at 0, veterans at their own counts) each joiner reproduces its solo
    solve bitwise. ``k`` keeps the veteran state's counter (informational;
    serving tracks per-row counts host-side). This is the state half of
    continuous admission; the plan half is :func:`repro.core.plan.join_rows`.
    """
    if state.key.ndim != 2 or new.key.ndim != 2:
        raise ValueError("join_state_rows splices stacked states (per-request "
                         "(R, 2) key stacks on both sides)")
    if state.hist.shape[0] != new.hist.shape[0]:
        raise ValueError(f"history length mismatch: {state.hist.shape[0]} vs "
                         f"{new.hist.shape[0]} (joiners must share the "
                         "group's plan family)")
    return concat_state_rows(state, new)


# ----------------------------------------------------- request-axis sharding
def _request_shardings(plan: SolverPlan, state: SamplerState, mesh):
    """(plan, state) NamedSharding trees for data-parallel stacked execution."""
    from ..sharding.rules import plan_specs, state_specs, to_shardings
    return (to_shardings(plan_specs(plan, mesh), mesh),
            to_shardings(state_specs(state, mesh), mesh))


def shard_state(plan: SolverPlan, state: SamplerState, mesh):
    """Place a stacked (plan, state) pair over ``mesh``'s data axis.

    Every request-axis leaf (x, eps history, the per-request key chains, and
    the plan's per-row coefficient stacks) is committed to a
    ``NamedSharding`` over the data-like axes; scalars replicate. Under a
    trace the placement becomes a sharding constraint instead of a transfer,
    so the same helper serves eager callers and jitted executors.
    """
    plan_sh, state_sh = _request_shardings(plan, state, mesh)
    leaves = jax.tree_util.tree_leaves((plan, state))
    if any(isinstance(l, jax.core.Tracer) for l in leaves):
        place = jax.lax.with_sharding_constraint
    else:
        place = jax.device_put
    return place(plan, plan_sh), place(state, state_sh)


# ------------------------------------------------------------------ steps
def _apply_eps(hooks: Hooks, x, t, eps):
    return eps if hooks.eps_transform is None else hooks.eps_transform(x, t, eps)


def _at_step(v, k, stacked: bool):
    """Per-step (or per-knot) leaf at step index ``k``.

    ``v[k]`` unstacked; ``v[:, k]`` stacked under a group-uniform scalar
    ``k``; ``v[arange(R), k]`` stacked under a per-row ``(R,)`` vector --
    the post-join case where each row runs at its own step count. The
    vector gather picks exactly the same elements a scalar index would when
    all entries agree, so uniform groups stay bitwise identical across the
    two forms."""
    if not stacked:
        return v[k]
    if jnp.ndim(k) == 0:
        return v[:, k]
    return v[jnp.arange(v.shape[0]), k]


def bcast(v, x):
    """Broadcast a per-request coefficient vector (R,) against x (R, *inner).
    No-op on scalars (unstacked plans). This is the stacked-plan broadcasting
    contract; eps oracles that support per-request time vectors (e.g.
    :class:`repro.diffusion.analytic.GaussianData`) share it."""
    return v.reshape(v.shape + (1,) * (x.ndim - v.ndim)) if jnp.ndim(v) else v


def _comb(w, hist, stacked: bool):
    """History combination: sum_j w[j] hist[j] (unstacked, w: (H,)) or
    per-request sum_j w[r, j] hist[j, r] (stacked, w: (R, H))."""
    if stacked:
        return jnp.einsum("rh,hr...->r...", w, hist)
    return jnp.tensordot(w, hist, axes=1)


def _update_err(loc, live, prev, stacked: bool):
    """Fold one step's embedded-pair difference ``loc`` into the running
    per-row estimate: Linf (max-abs over inner dims) where the companion
    weights were live, previous value elsewhere (warmup rows, inert/padded
    steps -- their zeroed weights would read as spurious convergence)."""
    axes = tuple(range(1, loc.ndim)) if stacked else None
    raw = jnp.max(jnp.abs(loc), axis=axes)
    return jnp.where(live, raw, prev)


def _split_keys(key, stacked: bool):
    """split() that treats a (R, 2) leaf as R independent per-request keys."""
    if stacked:
        ks = jax.vmap(jax.random.split)(key)   # (R, 2, 2)
        return ks[:, 0], ks[:, 1]
    return jax.random.split(key)


def _noise_like(sub, x, stacked: bool):
    """Per-request draws match what a single-request solve under keys[r]
    would draw: normal(keys[r], inner_shape) row by row."""
    if stacked:
        return jax.vmap(
            lambda kk: jax.random.normal(kk, x.shape[1:], x.dtype))(sub)
    return jax.random.normal(sub, x.shape, x.dtype)


def _fused_rows(xf, hf, psi_r, C_r, s_r, n_r, E_r, mesh):
    """The fused kernel over ``(R, M, D)`` rows, per shard under a mesh.

    A Mosaic kernel is opaque to XLA's SPMD partitioner, so under a
    request-axis mesh the call runs inside ``shard_map`` over the data
    axes: each device steps its own rows. The kernel computes every row
    independently, so this is exact -- bitwise the unsharded result."""
    ops = {"x": xf, "hist": hf, "psi": psi_r, "C": C_r}
    if n_r is not None:
        ops.update(s=s_r, noise=n_r)
    if E_r is not None:
        ops["E"] = E_r

    def run(o):
        return _fused_ab_step(o["x"], o["hist"], o["psi"], o["C"],
                              s=o.get("s"), noise=o.get("noise"),
                              err_coeffs=o.get("E"))

    if mesh is None:
        return run(ops)
    from ..sharding.rules import request_axis_spec
    specs = {name: request_axis_spec(v, mesh, 1 if name == "hist" else 0)
             for name, v in ops.items()}
    err_spec = specs["psi"] if E_r is not None else None   # (R,) like psi
    # check_vma=False: the kernel's out_shape carries no varying-axes
    # annotation; rows are independent, so every output is per-shard exact
    return jax.shard_map(run, mesh=mesh, in_specs=(specs,),
                         out_specs=(specs["x"], err_spec),
                         check_vma=False)(ops)


def _step_ab(plan: SolverPlan, k, state: SamplerState, eps_fn: EpsFn,
             hooks: Hooks, mesh=None) -> SamplerState:
    c, stk = plan.coeffs, plan.stacked
    x, key = state.x, state.key
    if plan.stochastic:
        key, sub = _split_keys(key, stk)
    t_k = _at_step(plan.ts, k, stk)
    psi = _at_step(c["psi"], k, stk)
    Cw = _at_step(c["C"], k, stk)
    if "nu" in c:
        # score-normalized families (sndeis): the polynomial was fitted to
        # eps/ell, so history entry j is weighted by C[k, j] * nu[k, j]
        nu = _at_step(c["nu"], k, stk)
        Cw = Cw * nu
    eps = _apply_eps(hooks, x, t_k, eps_fn(x, t_k))
    hist = jnp.concatenate([eps[None], state.hist[:-1]], axis=0)
    s_coef = noise = None
    if plan.stochastic:
        s_coef = _at_step(c["s"], k, stk)
        noise = _noise_like(sub, x, stk)
    Ew = live = None
    if "E" in c:
        Ew = _at_step(c["E"], k, stk)
        live = jnp.any(Ew != 0, axis=-1)
        if "nu" in c:
            Ew = Ew * nu          # the pair difference is normalized too
    if plan.fused:
        # Flatten to the kernel's (R, M, D) layout. Unstacked solves run as a
        # one-row stack, so solo and stacked groups share the same per-block
        # arithmetic (the serving bitwise-vs-solo invariant). Noise draw and
        # error-pair combination ride in the same kernel call: one HBM round
        # trip instead of r+3.
        n_rows = x.shape[0] if stk else 1
        inner = x.shape[1:] if stk else x.shape
        m = 1
        for dim in inner[:-1]:
            m *= dim
        d = inner[-1] if inner else 1
        xf = x.reshape(n_rows, m, d)
        hf = hist.reshape(hist.shape[0], n_rows, m, d)
        if stk:
            psi_r, C_r, s_r, E_r = psi, Cw, s_coef, Ew
        else:
            psi_r = jnp.reshape(psi, (1,))
            C_r = Cw[None]
            s_r = jnp.reshape(s_coef, (1,)) if s_coef is not None else None
            E_r = Ew[None] if Ew is not None else None
        n_r = noise.reshape(xf.shape) if noise is not None else None
        out, err_raw = _fused_rows(xf, hf, psi_r, C_r, s_r, n_r, E_r,
                                   mesh if stk else None)
        x_new = out.reshape(x.shape)
        if Ew is not None:
            raw = err_raw if stk else err_raw[0]
            err = jnp.where(live, raw.astype(state.err.dtype), state.err)
        else:
            err = state.err
    else:
        x_new = bcast(psi, x) * x + _comb(Cw, hist, stk)
        if plan.stochastic:
            x_new = x_new + bcast(s_coef, x) * noise
        if Ew is not None:
            err = _update_err(_comb(Ew, hist, stk), live, state.err, stk)
        else:
            err = state.err
    return SamplerState(x=x_new, hist=hist, key=key, k=state.k + 1, err=err)


def _step_rk(plan: SolverPlan, k, state: SamplerState, eps_fn: EpsFn,
             hooks: Hooks) -> SamplerState:
    c, stk = plan.coeffs, plan.stacked
    x = state.x
    n_stages = c["b"].shape[-1]
    h = _at_step(c["h"], k, stk)
    A_k = _at_step(c["A"], k, stk)                   # (R, S, S) / (S, S)
    stage_mu = _at_step(c["stage_mu"], k, stk)       # (R, S) / (S,)
    stage_t = _at_step(c["stage_t"], k, stk)
    y = x / bcast(_at_step(c["mu"], k, stk), x)
    ks = jnp.zeros((n_stages,) + x.shape, x.dtype)
    for i in range(n_stages):  # static unroll over stages
        y_i = y + bcast(h, x) * _comb(A_k[..., i, :], ks, stk)
        x_i = bcast(stage_mu[..., i], x) * y_i
        st_t = stage_t[..., i]
        k_i = _apply_eps(hooks, x_i, st_t, eps_fn(x_i, st_t))
        ks = ks.at[i].set(k_i)
    y = y + bcast(h, x) * _comb(c["b"], ks, stk)
    mu_next = _at_step(c["mu"], k + 1, stk)
    if "b_err" in c:
        # embedded pair difference, mapped to x-space through the same
        # mu-weighting the iterate gets
        loc = bcast(mu_next, x) * (bcast(h, x) * _comb(c["b_err"], ks, stk))
        err = _update_err(loc, h != 0, state.err, stk)
    else:
        err = state.err
    return SamplerState(x=bcast(mu_next, x) * y,
                        hist=state.hist, key=state.key, k=state.k + 1,
                        err=err)


_N_WARMUP = 3  # PNDM pseudo-RK4 warmup steps


def _pndm_warmup(plan: SolverPlan, k, state: SamplerState, eps_fn: EpsFn,
                 hooks: Hooks) -> SamplerState:
    """Pseudo-RK4 warmup step (4 NFE). ``k`` may be traced; warm-coefficient
    indices are clamped so the trace stays valid for any k (the tail branch
    of the traced `lax.cond` never executes this at k >= _N_WARMUP, and the
    per-row mixed path masks warm rows explicitly)."""
    c, stk = plan.coeffs, plan.stacked
    x = state.x
    if isinstance(k, jax.core.Tracer) or jnp.ndim(k):
        kw = jnp.minimum(k, _N_WARMUP - 1)
    else:
        kw = k
    t_c, t_m, t_n = (_at_step(plan.ts, k, stk), _at_step(c["warm_t_mid"], kw, stk),
                     _at_step(plan.ts, k + 1, stk))
    rm, cm = _at_step(c["warm_ratio_m"], kw, stk), _at_step(c["warm_coef_m"], kw, stk)
    rn, cn = _at_step(c["warm_ratio_n"], kw, stk), _at_step(c["warm_coef_n"], kw, stk)
    rm, cm = bcast(rm, x), bcast(cm, x)
    rn, cn = bcast(rn, x), bcast(cn, x)
    e1 = _apply_eps(hooks, x, t_c, eps_fn(x, t_c))
    x1 = rm * x + cm * e1
    e2 = _apply_eps(hooks, x1, t_m, eps_fn(x1, t_m))
    x2 = rm * x + cm * e2
    e3 = _apply_eps(hooks, x2, t_m, eps_fn(x2, t_m))
    x3 = rn * x + cn * e3
    e4 = _apply_eps(hooks, x3, t_n, eps_fn(x3, t_n))
    e_prime = (e1 + 2 * e2 + 2 * e3 + e4) / 6.0
    x_new = rn * x + cn * e_prime
    hist = jnp.concatenate([e1[None], state.hist[:-1]], axis=0)
    # warmup has no embedded pair: err passes through (stays +inf pre-tail)
    return SamplerState(x=x_new, hist=hist, key=state.key, k=state.k + 1,
                        err=state.err)


def _pndm_tail(plan: SolverPlan, k, state: SamplerState, eps_fn: EpsFn,
               hooks: Hooks) -> SamplerState:
    c, stk = plan.coeffs, plan.stacked
    x = state.x
    t_k = _at_step(plan.ts, k, stk)
    psi = _at_step(c["psi"], k, stk)
    Cw = _at_step(c["C"], k, stk)
    e = _apply_eps(hooks, x, t_k, eps_fn(x, t_k))
    hist = jnp.concatenate([e[None], state.hist[:-1]], axis=0)
    x_new = bcast(psi, x) * x + _comb(Cw, hist, stk)
    if "E" in c:
        Ew = _at_step(c["E"], k, stk)
        err = _update_err(_comb(Ew, hist, stk), jnp.any(Ew != 0, axis=-1),
                          state.err, stk)
    else:
        err = state.err
    return SamplerState(x=x_new, hist=hist, key=state.key, k=state.k + 1,
                        err=err)


def _pndm_rowwise(plan: SolverPlan, k, state: SamplerState, eps_fn: EpsFn,
                  hooks: Hooks) -> SamplerState:
    """Per-row ``k`` vector: rows of a post-join group may sit on either
    side of pndm's structural warmup/tail split. All-warmup and all-tail
    groups stage exactly one branch via nested ``lax.cond``; a genuinely
    mixed group computes both branches (5 net evals that step) and selects
    rows -- joins across the warmup boundary are correct, just not free."""
    warm = lambda st: _pndm_warmup(plan, k, st, eps_fn, hooks)
    tail = lambda st: _pndm_tail(plan, k, st, eps_fn, hooks)

    def mixed(st):
        w, t = warm(st), tail(st)
        m = bcast(k < _N_WARMUP, st.x)               # (R, 1, ...)
        return SamplerState(x=jnp.where(m, w.x, t.x),
                            hist=jnp.where(m[None], w.hist, t.hist),
                            key=st.key, k=st.k + 1,
                            err=jnp.where(k < _N_WARMUP, w.err, t.err))

    return jax.lax.cond(
        jnp.all(k < _N_WARMUP), warm,
        lambda st: jax.lax.cond(jnp.any(k < _N_WARMUP), mixed, tail, st),
        state)


def _step_pndm(plan: SolverPlan, k, state: SamplerState, eps_fn: EpsFn,
               hooks: Hooks) -> SamplerState:
    if jnp.ndim(k):
        return _pndm_rowwise(plan, k, state, eps_fn, hooks)
    if isinstance(k, jax.core.Tracer):
        # warmup and tail differ structurally (4 vs 1 net evals); under a
        # traced k both are staged and `lax.cond` executes only the taken
        # branch -- this is what lets serving jit ONE step for all k.
        return jax.lax.cond(
            k < _N_WARMUP,
            lambda st: _pndm_warmup(plan, k, st, eps_fn, hooks),
            lambda st: _pndm_tail(plan, k, st, eps_fn, hooks),
            state)
    k = int(k)  # repro: allow[RL001] eager path: traced k returned via lax.cond above
    if k < _N_WARMUP:
        return _pndm_warmup(plan, k, state, eps_fn, hooks)
    return _pndm_tail(plan, k, state, eps_fn, hooks)


_STEPPERS = {"ab": _step_ab, "rk": _step_rk, "pndm": _step_pndm}


def _stepper(method: str, mesh):
    """The step function for ``method``; the fused AB path needs the mesh
    to run its kernel per shard (:func:`_fused_rows`)."""
    if method == "ab" and mesh is not None:
        return functools.partial(_step_ab, mesh=mesh)
    return _STEPPERS[method]


def step(plan: SolverPlan, k, state: SamplerState, eps_fn: EpsFn, *,
         hooks: Optional[Hooks] = None, mesh=None) -> SamplerState:
    """Advance one solver step: ``state`` at time ``ts[k]`` -> ``ts[k+1]``.

    For a stacked plan ``k`` may be a per-row ``(R,)`` int vector: row ``i``
    steps from ITS index ``k[i]`` (a serving group whose rows were admitted
    at different ticks). Entries are clamped to the plan's grid, so a row
    riding past its own horizon indexes only inert padded coefficients.

    ``mesh`` (a ``jax.sharding.Mesh`` with a data-like axis) places the
    stacked request axis of every state/plan leaf with a ``NamedSharding``
    before stepping -- data-parallel execution over requests. Sharding never
    changes WHAT is computed (row ``i`` is row ``i``'s solo solve, bitwise);
    serving's AOT executors jit with explicit in/out shardings and pass
    their mesh here too, so a fused plan's kernel runs per shard.
    """
    plan = plan.astype(state.x.dtype)
    if jnp.ndim(k):
        if not plan.stacked:
            raise ValueError("a per-row k vector requires a stacked plan")
        k = jnp.minimum(jnp.asarray(k, jnp.int32), plan.n_steps - 1)
    if mesh is not None:
        plan, state = shard_state(plan, state, mesh)
    return _stepper(plan.method, mesh)(plan, k, state, eps_fn,
                                       hooks or _DEFAULT_HOOKS)


def step_evals(plan: SolverPlan, ks) -> int:
    """Calls of ``eps_fn`` one :func:`step` of the stacked ``plan`` runs at
    the host per-row step vector ``ks``: one for ab, the stage count for
    rk; pndm runs its 4-eval warmup branch, its 1-eval tail, or both when
    its rows sit on either side of the split."""
    if plan.method == "rk":
        return plan.coeffs["b"].shape[-1]
    if plan.method == "pndm":
        warm = [min(k, plan.n_steps - 1) < _N_WARMUP for k in ks]
        return 4 * any(warm) + (not all(warm))
    return 1


def sample(plan: SolverPlan, eps_fn: EpsFn, x_T: Array,
           key: Optional[Array] = None, *, hooks: Optional[Hooks] = None,
           mesh=None):
    """Run the full solve from ``x_T`` at ``ts[0]`` down to ``ts[-1]``.

    Returns ``x_0``, or ``(x_0, trajectory)`` if ``hooks.record_trajectory``.

    ``mesh`` shards a *stacked* solve's request axis over the mesh's
    data-like axes before the loop; sharding propagates through the loop
    body, so every step runs data-parallel over requests. Rows never mix:
    in float32 (the serving dtype) results are bitwise identical to the
    single-device solve; under float64 the SPMD-partitioned loop body may
    fuse differently and differ by 1 ulp (the same caveat as ``sample`` vs
    an eagerly dispatched ``step`` loop). Serving's per-step AOT executors
    are bitwise on both paths.
    """
    hooks = hooks or _DEFAULT_HOOKS
    state = init_state(plan, x_T, key)
    plan = plan.astype(x_T.dtype)
    if mesh is not None:
        plan, state = shard_state(plan, state, mesh)
    n = plan.n_steps
    stepper = _stepper(plan.method, mesh)

    # pndm's warmup/tail differ structurally, so it always unrolls
    if plan.method == "pndm":
        traj = []
        for k in range(n):
            state = stepper(plan, k, state, eps_fn, hooks)
            if hooks.record_trajectory:
                traj.append(state.x)
        return (state.x, jnp.stack(traj)) if hooks.record_trajectory else state.x

    if hooks.record_trajectory:
        traj0 = jnp.zeros((n,) + x_T.shape, x_T.dtype)

        def body_t(k, carry):
            st, traj = carry
            st = stepper(plan, k, st, eps_fn, hooks)
            return st, traj.at[k].set(st.x)

        state, traj = jax.lax.fori_loop(0, n, body_t, (state, traj0))
        return state.x, traj

    state = jax.lax.fori_loop(
        0, n, lambda k, st: stepper(plan, k, st, eps_fn, hooks), state)
    return state.x
