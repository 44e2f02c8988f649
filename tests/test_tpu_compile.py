"""Compile the served path's kernels and step for a described TPU v5e.

Nothing runs: each test lowers and compiles at real widths for a chip that
is described, not attached, so Mosaic refuses here what it would refuse on
the chip (block shapes off the (8, 128) tiling, primitives it cannot lower,
programs that do not fit). Kernels are forced compiled: on this host
``jax.default_backend()`` is the CPU, which would otherwise pick the
interpreter. Every compiled program must hold the kernel
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite runs in several.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.adaptive import RetirePolicy
from repro.core.plan import stack_plans
from repro.diffusion import lm as DLM
from repro.kernels import runtime
from repro.kernels.deis_step import fused_ab_step
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_experts import moe_experts
from repro.kernels.ssd_scan import ssd_scan
from repro.models import transformer as T
from repro.serving.engine import DiffusionServeEngine

V5E_HBM_BYTES = 16e9
R, SEQ = 8, 256                      # served group rows, sequence length


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # conftest turns on x64 for the numerics tests; programs run on the
    # chip without it (Mosaic takes no 64-bit grid indices)
    try:
        with jax.enable_x64(False):
            yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels_compiled(monkeypatch):
    monkeypatch.setattr(runtime, "default_interpret",
                        lambda kernel="deis_step": False)


def _shapes(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` (one sharding, or
    a tree of them matching ``tree``)."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("form", ["plain", "noise", "err"])
def test_fused_ab_step_compiles(one_chip, form):
    """The stacked AB update at the served danube shape: R=8 rows of
    (256, 3840) f32 iterates, a 3-deep eps history."""
    r, d = 3, 3840
    x = jax.ShapeDtypeStruct((R, SEQ, d), jnp.float32, sharding=one_chip)
    hist = jax.ShapeDtypeStruct((r, R, SEQ, d), jnp.float32,
                                sharding=one_chip)
    row = jax.ShapeDtypeStruct((R,), jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((R, r), jnp.float32, sharding=one_chip)
    if form == "plain":
        _compile(lambda x, h, p, c: fused_ab_step(x, h, p, c,
                                                  interpret=False),
                 x, hist, row, table)
    elif form == "noise":
        _compile(lambda x, h, p, c, s, n: fused_ab_step(
            x, h, p, c, s=s, noise=n, interpret=False),
            x, hist, row, table, row, x)
    else:
        _compile(lambda x, h, p, c, e: fused_ab_step(
            x, h, p, c, err_coeffs=e, interpret=False),
            x, hist, row, table, table)


def test_flash_attention_compiles(one_chip):
    """h2o-danube widths: 32 query heads over 8 KV heads (GQA), head_dim
    120, sliding window 4096, bidirectional as the eps-net runs it."""
    cfg = get_config("h2o_danube_3_4b")
    hd = cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((R, SEQ, cfg.n_heads, hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((R, SEQ, cfg.n_kv_heads, hd), jnp.bfloat16,
                              sharding=one_chip)
    _compile(lambda q, k, v: flash_attention(
        q, k, v, causal=False, window=cfg.sliding_window, interpret=False),
        q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_compiles(one_chip, dtype):
    """mamba2-2.7b widths: d_inner 5120 as 80 heads of 64, state 128,
    chunk 256."""
    ssm = get_config("mamba2_2p7b").ssm
    d_inner = ssm.expand * get_config("mamba2_2p7b").d_model
    h, p, n, s = d_inner // ssm.head_dim, ssm.head_dim, ssm.state_dim, 1024
    x = jax.ShapeDtypeStruct((1, s, h, p), dtype, sharding=one_chip)
    a = jax.ShapeDtypeStruct((1, s, h), jnp.float32, sharding=one_chip)
    bc = jax.ShapeDtypeStruct((1, s, n), dtype, sharding=one_chip)
    _compile(lambda x, a, B, C: ssd_scan(x, a, B, C, chunk=ssm.chunk_size,
                                         interpret=False), x, a, bc, bc)


@pytest.mark.parametrize("tokens", [512, 256])
def test_moe_experts_compiles(one_chip, tokens):
    """The held experts of sdar-30b-a3b's 8-chip share: 16 of 128 experts
    of width 768 over d_model 2048, every one of the 48 layers' stacks in
    the call, for a 2-row tile at seq 256 and at seq 128. The custom call
    keeps its name, which the benchmark's trace reader finds it by."""
    layers, held, d, f = 48, 16, 2048, 768

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = _compile(
        lambda x, g, wg, wu, wd, layer: moe_experts(
            x, g, wg, wu, wd, layer, per_token=8, interpret=False),
        sds((tokens, d)), sds((tokens, held), jnp.float32),
        sds((layers, held, d, f)), sds((layers, held, d, f)),
        sds((layers, held, f, d)), sds((), jnp.int32))
    assert "%_moe_experts" in compiled.as_text()


def _danube(n_layers=None):
    cfg = get_config("h2o_danube_3_4b").with_(objective="diffusion")
    if n_layers is not None:
        cfg = cfg.with_(n_layers=n_layers)
    return cfg, jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))


def _group(eng, cfg, solver, seq=SEQ):
    """A stacked R-row group of ``solver`` at ``seq``: (signature, plan,
    state) as the engine's admission builds them, as shapes."""
    plan = eng._plan(solver, 10, None)
    stacked = stack_plans([plan] * R)
    state = jax.eval_shape(lambda: DLM.init_sample_state(
        cfg, stacked, DLM.request_keys(range(R)), seq_len=seq,
        prior_std=1.0))
    return plan.signature, stacked, state


@pytest.mark.parametrize("solver", ["tab3", "seeds2"])
def test_served_danube_step_compiles(one_chip, kernels_compiled, solver):
    """One step of the engine's own AOT executor for h2o-danube-3-4b at
    full width and depth, bf16, R=8 rows of seq 256: it compiles with the
    fused kernel in it and fits one v5e's HBM."""
    cfg, pshape = _danube()
    eng = DiffusionServeEngine(_shapes(pshape, one_chip), cfg)
    sig, plan, state = _group(eng, cfg, solver)
    compiled, _ = eng._executor(sig, _shapes(plan, one_chip),
                                _shapes(state, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


@pytest.mark.parametrize("seq", [32, 64])
def test_served_danube_short_bucket_step_compiles(one_chip, kernels_compiled,
                                                  seq):
    """The same executor at the short buckets, whose eps tiles hold 8 and
    4 rows (``lm.tile_rows``): R=8 rows of seq 32 or 64 compile with the
    fused kernel and fit one v5e."""
    cfg, pshape = _danube()
    eng = DiffusionServeEngine(_shapes(pshape, one_chip), cfg)
    sig, plan, state = _group(eng, cfg, "tab3", seq)
    compiled, _ = eng._executor(sig, _shapes(plan, one_chip),
                                _shapes(state, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_served_sdar_step_compiles(one_chip, kernels_compiled):
    """One step of the engine's AOT executor for sdar-30b-a3b at its
    published widths and depth as one chip's share of an 8-chip expert-
    parallel deployment (16 of 128 experts held), bf16, R=8 rows of seq
    256: the expert kernel and the fused AB kernel are in it, the step
    returns the held-expert counts beside the state, and the 10.4 GB of
    weights (all but the embedding table are the step's arguments) and
    its temporaries fit one v5e."""
    cfg = get_config("sdar_30b_a3b", moe={"experts_held": 16}).with_(
        objective="diffusion")
    pshape = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    eng = DiffusionServeEngine(_shapes(pshape, one_chip), cfg)
    sig, plan, state = _group(eng, cfg, "tab3")
    compiled, _ = eng._executor(sig, _shapes(plan, one_chip),
                                _shapes(state, one_chip))
    text = compiled.as_text()
    assert "%_moe_experts" in text and "%_fused_ab_jit" in text
    mem = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pshape))
    assert 10.3e9 < weights < 10.5e9
    assert mem.argument_size_in_bytes > weights - 2 * pshape["embed"].size
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


@pytest.mark.parametrize("retire", [None, RetirePolicy(tol=1e-3)])
def test_sharded_danube_step_compiles(topo, kernels_compiled, monkeypatch,
                                      retire):
    """The engine's own request-axis data-parallel executor on four
    described chips: the fused kernel (with its error-pair output under a
    RetirePolicy) runs per shard (``shard_map``), and the step needs no
    collective. Depth cut to two layers; widths as published."""
    cfg, pshape = _danube(n_layers=2)
    mesh = Mesh(topo.devices, ("data",), axis_types=(AxisType.Auto,))
    with monkeypatch.context() as m:
        # described chips hold no buffers: the engine's replication of the
        # params onto the mesh places shapes instead
        m.setattr(jax, "device_put", _shapes)
        eng = DiffusionServeEngine(pshape, cfg, mesh=mesh, retire=retire)
    assert all(isinstance(a.sharding, NamedSharding)
               for a in jax.tree.leaves(eng._params_exec))
    sig, plan, state = _group(eng, cfg, "tab3")
    plan_sh, state_sh = eng._shardings(plan, state)
    compiled, _ = eng._executor(sig, _shapes(plan, plan_sh),
                                _shapes(state, state_sh))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text
