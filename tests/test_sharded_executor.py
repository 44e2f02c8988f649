"""Data-parallel sharded stacked executor: request-axis spec rules,
mesh-arg sampling, engine integration on a degenerate 1-device mesh, and
(subprocess, 8 forced host devices) bitwise equality of the sharded serving
path with the single-device path -- including mid-flight compaction with
zero warm recompiles.

The multi-device cases must run in a subprocess because
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` only takes effect
BEFORE jax is imported (conftest already imported it here).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import (VPSDE, get_timesteps, inert_row, init_state,
                        make_plan, sample, stack_plans, step)
from repro.diffusion.analytic import GaussianData
from repro.launch.mesh import make_request_mesh, mesh_fingerprint
from repro.sharding import rules as R

SDE = VPSDE()
TS = get_timesteps(SDE, 6, "quadratic")


class FakeMesh:
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


def _problem(batch):
    # float32 like the serving stack: under x64, placing committed shardings
    # can change XLA's fori_loop fusion by 1 ulp (the per-step AOT executors
    # serving uses are bitwise either way; see sample()'s mesh docstring)
    g = GaussianData(SDE, mean=np.full(4, 1.5), var=np.full(4, 0.25))
    xT = jax.random.normal(jax.random.PRNGKey(0), (batch, 4),
                           jnp.float32) * SDE.prior_std()
    raw = g.eps_fn()
    return (lambda x, t: raw(x, t).astype(x.dtype)), xT


# ------------------------------------------------------------- spec rules
def test_plan_specs_shard_request_axis_when_divisible():
    mesh = FakeMesh(data=4)
    plan = stack_plans([make_plan("tab2", SDE, TS)] * 4)
    specs = R.plan_specs(plan, mesh)
    assert specs.ts == P("data", None)
    assert all(s[0] == "data" for s in specs.coeffs.values())
    # non-divisible batch falls back to replication leaf-wise
    plan3 = stack_plans([make_plan("tab2", SDE, TS)] * 3)
    specs3 = R.plan_specs(plan3, mesh)
    assert specs3.ts == P(None, None)
    # unstacked plans replicate entirely
    specs1 = R.plan_specs(make_plan("tab2", SDE, TS), mesh)
    assert specs1.ts == P()


def test_plan_specs_cover_new_family_coeff_leaves():
    """The spec rule is leaf-generic: the sndeis per-step ``nu`` key, the
    seeds noise scale ``s``, the scire stage tableaus and the lambda-basis
    dpm tables all pick up the request axis on a stacked plan -- there is no
    per-family spec table to fall out of date."""
    mesh = FakeMesh(data=4)
    for name in ("sndeis2", "seeds1", "scire2", "dpm3m"):
        plan = stack_plans([make_plan(name, SDE, TS)] * 4)
        specs = R.plan_specs(plan, mesh)
        assert specs.ts == P("data", None)
        assert set(specs.coeffs) == set(plan.coeffs)
        for key_, s in specs.coeffs.items():
            assert s[0] == "data", (name, key_, s)
    assert "nu" in R.plan_specs(
        stack_plans([make_plan("sndeis2", SDE, TS)] * 4), mesh).coeffs


def test_state_specs_layout():
    """x shards on axis 0, hist on axis 1 (history axis leads), keys on
    axis 0, step counter replicates."""
    mesh = FakeMesh(data=2)
    plan = stack_plans([make_plan("em", SDE, TS)] * 2)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in (1, 2)])
    _, xT = _problem(batch=2)
    st = init_state(plan, xT, keys)
    specs = R.state_specs(st, mesh)
    assert specs.x == P("data", None)
    assert specs.hist == P(None, "data", None)
    assert specs.key == P("data", None)
    assert specs.k == P()
    # unstacked state (single PRNG key) replicates
    solo = init_state(make_plan("em", SDE, TS), xT[0], jax.random.PRNGKey(0))
    s1 = R.state_specs(solo, mesh)
    assert s1.x == P() and s1.key == P()


def test_inert_row_is_inert_and_stackable():
    """An inert filler row has the member's signature, zero weight-like
    coefficients (its iterate update is the zero map, its noise scale zero),
    and in-domain times -- stepping it stays finite forever."""
    for name in ("tab2", "em", "rho_heun", "pndm"):
        plan = make_plan(name, SDE, get_timesteps(
            SDE, 8 if name == "pndm" else 6, "quadratic"))
        filler = inert_row(plan)
        assert filler.signature == plan.signature and filler.nfe == 0
        stacked = stack_plans([plan, filler])
        eps, xT = _problem(batch=2)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in (1, 0)])
        st = init_state(stacked, xT, keys)
        for k in range(stacked.n_steps):
            st = step(stacked, k, st, eps)
        assert np.all(np.isfinite(np.asarray(st.x)))
        # the real row is untouched by the filler riding along (sample vs
        # sample: the step-loop differs from fori_loop by fusion only)
        full = sample(stacked, eps, xT, keys)
        solo = sample(stack_plans([plan]), eps, xT[:1], keys[:1])
        np.testing.assert_array_equal(np.asarray(full[0]),
                                      np.asarray(solo[0]))


# In the full tier-1 run the suite executes with a forced host device count
# (test_dryrun_units imports repro.launch.dryrun at collection, which sets
# XLA_FLAGS before backends initialize), so in-process meshes must cap their
# data axis rather than assume 1 device.
def _small_mesh():
    return make_request_mesh(min(jax.device_count(), 4))


def test_mesh_fingerprint_distinguishes_layouts():
    m1 = _small_mesh()
    assert mesh_fingerprint(m1) == mesh_fingerprint(_small_mesh())
    fp = mesh_fingerprint(m1)
    assert fp[0] == (("data", min(jax.device_count(), 4)),)


# ------------------------------------------------- mesh-arg sample()/step()
def test_sample_and_step_with_mesh_equal_unsharded():
    """On however many devices exist (1 in the default test env), the mesh
    arg never changes WHAT is computed: the per-step path (what serving
    executes) is bit-identical with and without the mesh; the full-solve
    ``fori_loop`` matches to machine epsilon (the SPMD partitioner may fuse
    the loop body differently -- the same caveat as ``sample`` vs an eagerly
    dispatched ``step`` loop, see the sampler module docstring)."""
    mesh = _small_mesh()
    n = min(jax.device_count(), 4)
    plans = [make_plan("em", SDE, TS)] * n + [make_plan("em", SDE, TS)] * n
    eps, xT = _problem(batch=2 * n)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(2 * n)])
    stacked = stack_plans(plans)
    st_plain = init_state(stacked, xT, keys)
    st_mesh = init_state(stacked, xT, keys)
    for k in range(stacked.n_steps):
        st_plain = step(stacked, k, st_plain, eps)
        st_mesh = step(stacked, k, st_mesh, eps, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(st_mesh.x),
                                  np.asarray(st_plain.x))
    want = sample(stacked, eps, xT, keys)
    got = sample(stacked, eps, xT, keys, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-7, atol=3e-7)


# --------------------------------------------- engine on a degenerate mesh
@pytest.fixture(scope="module")
def diff_setup():
    from repro.configs.base import get_config
    from repro.models import transformer as T
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def test_engine_mesh_counts_every_devices_tiles(diff_setup):
    """Under a request mesh each device loops over its own rows in tiles
    of the bucket's width (8 rows at seq 32): ``serve_eps_tiles_total``
    counts every device's trips, once per group step."""
    from repro.diffusion import lm as DLM
    from repro.serving.engine import DiffusionServeEngine, Request
    params, cfg = diff_setup
    n = min(jax.device_count(), 4)
    eng = DiffusionServeEngine(params, cfg, mesh=_small_mesh())
    out = eng.serve([Request(uid=i, seq_len=32, nfe=4, solver="tab2",
                             seed=i) for i in range(3)])
    assert len(out) == 3
    rows = -(-3 // n) * n                    # rounded up to the data axis
    per_step = n * -(-(rows // n) // DLM.tile_rows(32))
    assert eng.metrics.counter("serve_eps_tiles_total").value \
        == eng.ticks * per_step
    assert eng.metrics.counter("serve_eps_tile_rows_total").value \
        == eng.ticks * per_step * DLM.tile_rows(32)


def test_engine_mesh_bitwise_equals_unsharded(diff_setup):
    """A small ('data',) mesh (1 device standalone; up to 4 when the suite
    runs under a forced host device count) exercises the whole sharded code
    path -- NamedSharding placements, sharded AOT executors, mesh-keyed
    compile cache, group-size rounding -- and must reproduce the unsharded
    engine bit-for-bit, warm with zero recompiles."""
    from repro.serving.engine import DiffusionServeEngine, Request
    params, cfg = diff_setup
    reqs = [Request(uid=i, seq_len=16, nfe=[3, 6][i % 2],
                    solver=["ddim", "euler"][i % 2], seed=i)
            for i in range(4)]
    base = DiffusionServeEngine(params, cfg)
    want = {r.uid: r.tokens for r in base.serve(list(reqs))}
    eng = DiffusionServeEngine(params, cfg, mesh=_small_mesh())
    got = {r.uid: r.tokens for r in eng.serve(list(reqs))}
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    # cache keys carry the mesh fingerprint; warm serve never recompiles
    assert all(k[3] is not None for k in eng._compiled)
    n = eng.num_executors
    again = {r.uid: r.tokens for r in eng.serve(list(reqs))}
    assert eng.num_executors == n
    for uid in want:
        np.testing.assert_array_equal(again[uid], want[uid])


# ----------------------------------------- 8-device host mesh (subprocess)
_CHILD_COMMON = """
import os
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
"""

_CHILD_SAMPLER = _CHILD_COMMON + """
import jax.numpy as jnp
from repro.core import VPSDE, get_timesteps, make_plan, sample, stack_plans
from repro.diffusion.analytic import GaussianData
from repro.launch.mesh import make_request_mesh

SDE = VPSDE()
TS = get_timesteps(SDE, 6, "quadratic")
g = GaussianData(SDE, mean=np.full(4, 1.5), var=np.full(4, 0.25))
eps = g.eps_fn()
xT = jax.random.normal(jax.random.PRNGKey(0), (8, 4)) * SDE.prior_std()
# stacked STOCHASTIC plans with distinct per-request seeds: the sharded solve
# must reproduce each row's key chain exactly
plans = [make_plan("em", SDE, TS) if i % 2 else
         make_plan("ddim_eta", SDE, TS, eta=1.0) for i in range(8)]
keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(8)])
stacked = stack_plans(plans)
want = sample(stacked, eps, xT, keys)
got = sample(stacked, eps, xT, keys, mesh=make_request_mesh())
np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
# and each sharded row equals its SOLO single-device solve (seed contract)
for i in range(8):
    solo = sample(stack_plans([plans[i]]), eps, xT[i:i+1], keys[i:i+1])
    np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(solo[0]))
print("SAMPLER_OK")
"""

_CHILD_ENGINE = _CHILD_COMMON + """
from repro.configs.base import get_config
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine, Request
from repro.launch.mesh import make_request_mesh

cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
params = T.init_params(cfg, jax.random.PRNGKey(0))
# a mesh whose data axis exceeds max_group is unsatisfiable (the smallest
# placeable group would break the bound) and must be rejected at init
try:
    DiffusionServeEngine(params, cfg, max_group=4, mesh=make_request_mesh())
except ValueError as e:
    assert "max_group" in str(e)
else:
    raise AssertionError("max_group < data-axis size must raise")
# ragged NFE so rows retire mid-flight; max_group=16 > data axis 8 so
# compaction crosses a multiple boundary (16 -> 8) UNDER sharding; em rows
# make the group stochastic with distinct seeds
reqs = [Request(uid=i, seq_len=16, nfe=[3, 7][i % 2], solver="ddim", seed=i)
        for i in range(12)]
reqs += [Request(uid=100 + i, seq_len=16, nfe=4, solver="em", seed=50 + i)
         for i in range(3)]
base = DiffusionServeEngine(params, cfg, max_group=16)
want = {r.uid: r.tokens for r in base.serve(list(reqs))}
eng = DiffusionServeEngine(params, cfg, max_group=16,
                           mesh=make_request_mesh())
got = {r.uid: r.tokens for r in eng.serve(list(reqs))}
assert want.keys() == got.keys()
for uid in want:
    np.testing.assert_array_equal(got[uid], want[uid])
batches = sorted(k[1] for k in eng._compiled)
assert all(b % 8 == 0 for b in batches), batches   # groups place evenly
assert 8 in batches and 16 in batches, batches     # compaction hit 16 -> 8
# warm pass: compaction-under-sharding reuses the mesh-keyed cache -- zero
# recompiles -- and stays bitwise
n = eng.num_executors
again = {r.uid: r.tokens for r in eng.serve(list(reqs))}
assert eng.num_executors == n, "warm sharded serve recompiled"
for uid in want:
    np.testing.assert_array_equal(again[uid], want[uid])
# a ragged group pinned at the smallest placeable multiple (exactly 8 real
# rows on the 8-way axis, so compaction can never shrink it): retired rows
# become structural filler, not waste -- same status as compaction-retained
# rows
pinned = DiffusionServeEngine(params, cfg, mesh=make_request_mesh())
pinned.serve([Request(uid=200 + i, seq_len=16, nfe=[3, 7][i % 2],
                      solver="ddim", seed=i) for i in range(8)])
assert pinned.wasted_row_steps == 0, pinned.wasted_row_steps
print("ENGINE_OK")
"""


_CHILD_JOIN = _CHILD_COMMON + """
from repro.configs.base import get_config
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine, Request
from repro.launch.mesh import make_request_mesh

cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
params = T.init_params(cfg, jax.random.PRNGKey(0))
# an 8-row ragged group on the 8-way axis: 4 rows retire after 3 steps,
# leaving 4 slots; 4 joiners then refill them at the SAME batch size --
# the never-drain, never-recompile steady state
first = [Request(uid=i, seq_len=16, nfe=[3, 8][i % 2], solver="ddim", seed=i)
         for i in range(8)]
late = [Request(uid=100 + i, seq_len=16, nfe=4, solver="euler", seed=50 + i)
        for i in range(4)]
eng = DiffusionServeEngine(params, cfg, max_group=8, mesh=make_request_mesh())
out = []
for r in first:
    eng.submit(r)
for _ in range(3):
    out += eng.tick()              # nfe=3 rows retire at tick 3
for r in late:
    eng.submit(r)
while eng.busy:
    out += eng.tick()
assert eng.joined_requests == 4, eng.joined_requests
assert eng.wasted_row_steps == 0, eng.wasted_row_steps
# retired rows became join slots in place: ONE executor bucket, batch 8
assert {k[1] for k in eng._compiled} == {8}, sorted(eng._compiled)
got = {r.uid: r.tokens for r in out}
assert len(got) == 12
solo = DiffusionServeEngine(params, cfg)   # single-device solo reference
for r in first + late:
    np.testing.assert_array_equal(
        solo.serve([Request(uid=r.uid, seq_len=16, nfe=r.nfe,
                            solver=r.solver, seed=r.seed)])[0].tokens,
        got[r.uid])
print("JOIN_OK")
"""


def _run_child(script: str, marker: str, timeout: int) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    assert marker in out.stdout, out.stdout


def test_8dev_sampler_bitwise_stochastic_stack():
    """Forced 8-device host mesh: a stacked stochastic solve (em + ddim_eta,
    distinct seeds) sharded over the request axis is bitwise identical to the
    unsharded stack AND to each row's solo solve."""
    _run_child(_CHILD_SAMPLER, "SAMPLER_OK", timeout=600)


@pytest.mark.slow  # compiles an 8-row sharded executor + solo references
def test_8dev_engine_join_refills_group_at_fixed_batch():
    """Forced 8-device host mesh: retired rows of an 8-row ragged group
    become join slots -- late same-family requests are spliced in at the
    SAME batch size (one executor bucket total, zero waste) and every
    sample, veteran and joiner, is bitwise-identical to a single-device
    solo serve."""
    _run_child(_CHILD_JOIN, "JOIN_OK", timeout=900)


@pytest.mark.slow  # compiles 16- and 8-row sharded+unsharded executors (~3min)
def test_8dev_engine_compaction_under_sharding_zero_recompiles():
    """Forced 8-device host mesh, serving layer: ragged groups round up to
    multiples of 8 with inert filler, compact 16 -> 8 mid-flight under
    sharding, produce bitwise-identical samples to the single-device engine,
    and a warm pass runs with zero recompiles."""
    _run_child(_CHILD_ENGINE, "ENGINE_OK", timeout=900)
