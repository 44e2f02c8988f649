"""The sparse-expert configuration's yardstick on the CPU: its plain
reference (``references/moe.py``) against the program in float32 at a tiny
size, its operation and byte counts, the readers of its three metrics, and
the cell's command end to end at a tiny width (the float8 control fails
it, the served path passes)."""
import io
import json
import pathlib
import re
import sys
from contextlib import redirect_stderr, redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from chipbench import cell as C  # noqa: E402
from chipbench import check, cost, cost_moe, model, readers_moe  # noqa: E402
from chipbench import spec, tracing, traffic  # noqa: E402

CELL = "sdar-30b-a3b-ep8.chat-s256"
CONF = json.loads((BENCH / "configs" / "sdar-30b-a3b-ep8.json").read_text())
DEV = "/device:TPU:0"
MS = 1e6                                  # ns


def _ref():
    return spec._load_py(BENCH / "references" / "moe.py")


def _tiny(dtype="float32", held=4, offset=2):
    """The configuration at a tiny width, 4 of 8 experts held, as the
    program runs it and as the reference reads it."""
    m = dict(CONF["model"], n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
             head_dim=16, vocab_size=128, dtype=dtype,
             moe=dict(CONF["model"]["moe"], num_experts=8, top_k=2,
                      expert_d_ff=32, experts_held=held,
                      expert_offset=offset))
    from repro.configs.base import get_config
    cfg = get_config(CONF["arch"], **{k: v for k, v in m.items()}).with_(
        objective="diffusion")
    return m, cfg


def test_the_file_is_the_program_config_with_its_cut():
    """``program_config`` accepts the file unchanged: the published widths
    with 16 of the 128 experts held; every number of the catalog's config
    sits at the top level under its own key."""
    cfg = model.program_config(CONF)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.vocab_size) == (48, 2048, 32, 4, 128,
                                                      151936)
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.expert_width,
            cfg.moe.experts_held, cfg.moe.dropless) == (128, 8, 768, 16, True)
    assert cfg.qk_norm and CONF["norm_topk_prob"]
    assert CONF["num_experts"] == 128 and CONF["moe_intermediate_size"] == 768
    assert CONF["reduced"] == ["moe.experts_held"]


def test_param_count_matches_the_program_layout():
    """10.37 GB of weights: 5.19 G parameters, the router's in float32."""
    layout = model.param_layout(model.program_config(CONF))
    leaves = jax.tree.leaves(layout)
    assert sum(a.size for a in leaves) == cost_moe.param_count(CONF["model"])
    assert 10.3e9 < sum(a.size * a.dtype.itemsize for a in leaves) < 10.4e9


def test_tile_flops_match_the_arithmetic():
    """About 1.27 TFLOP for a 2-row tile at seq 256 and NFE, with one
    held-expert assignment per position (8 of 128 experts, 16 held)."""
    m = CONF["model"]
    f = 2 * cost_moe.row_forward_flops(m, 256, 1.0)
    assert 1.2e12 < f < 1.35e12
    extra = cost_moe.layer_flops(m, 256, 2.0) - cost_moe.layer_flops(
        m, 256, 1.0)
    assert extra == 256 * 2 * 2048 * 768 * 3


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 2)])
def test_program_eps_and_logits_match_the_reference(held, offset):
    """In float32: the eps-net with q/k norm and the held experts' share of
    the MoE, over rows with a masked tail, and the decode logits."""
    m, cfg = _tiny(held=held, offset=offset)
    from repro.diffusion import lm as DLM
    from repro.models import transformer as T
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    ref = _ref()
    mm = check.make_mm("f32")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64), jnp.float32)
    t = jnp.array([0.3, 0.8], jnp.float32)
    vl = jnp.array([24, 17], jnp.int32)
    got = DLM.make_eps_fn(params, cfg, valid_len=vl)(x, t)
    m_ref = dict(m, head_dim=16)
    want = ref.eps(params, m_ref, CONF["diffusion"], x, t, vl, mm)
    for r, n in enumerate([24, 17]):
        np.testing.assert_allclose(np.asarray(got[r, :n]),
                                   np.asarray(want[r, :n]), rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(want))))
    x0 = 25.0 * jax.random.normal(jax.random.PRNGKey(3), (24, 64))
    lg = ref.logits(params, CONF["diffusion"], x0, mm)
    np.testing.assert_allclose(np.asarray((x0 / DLM.X0_SCALE)
                                          @ params["lm_head"]),
                               np.asarray(lg), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(DLM.decode_tokens(params, cfg,
                                                               x0)),
                                  np.asarray(jnp.argmax(lg, -1)))


def test_reference_shares_add_up_to_the_uncut_layer():
    """The reference's own share arithmetic: 4 shares of 2 experts add up
    to the layer that holds all 8."""
    ref = _ref()
    mm = check.make_mm("f32")
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    f = {"router": jax.random.normal(ks[0], (64, 8)) / 8,
         "w_gate": jax.random.normal(ks[1], (8, 64, 32)) / 8,
         "w_up": jax.random.normal(ks[2], (8, 64, 32)) / 8,
         "w_down": jax.random.normal(ks[3], (8, 32, 64)) / 6}
    h = jax.random.normal(ks[4], (2, 10, 64))
    moe = dict(CONF["model"]["moe"], num_experts=8, top_k=2, experts_held=8,
               expert_offset=0)
    whole = ref.experts(f, moe, h, mm)
    parts = sum(ref.experts(
        dict(f, **{k: v[2 * s:2 * s + 2] for k, v in f.items()
                   if k != "router"}),
        dict(moe, experts_held=2, expert_offset=2 * s), h, mm)
        for s in range(4))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------- readers
KERNEL = ("%_moe_experts.3 = f32[512,2048]{1,0:T(8,128)S(1)} custom-call("
          "s32[1]{0} %a, s32[47]{0} %b, s32[1]{0} %c, bf16[512,2048]{1,0} %x, "
          "s32[47,128,1]{2,1,0} %i, s32[47,1,128]{2,1,0} %j, "
          "f32[47,128,1]{2,1,0} %g, bf16[48,16,2048,768]{3,2,1,0} %wg, "
          "bf16[48,16,2048,768]{3,2,1,0} %wu, bf16[48,16,768,2048]{3,2,1,0} "
          "%wd), custom_call_target=\"tpu_custom_call\"")
CALL_BYTES = (2 * 4 + 47 * 4 + 512 * 2048 * 2 + 3 * 47 * 128 * 4
              + 3 * 16 * 2048 * 768 * 2)


def test_expert_call_bytes_count_one_layer_of_the_stacks():
    assert cost_moe.expert_call_bytes(KERNEL) == CALL_BYTES
    assert cost_moe.expert_call_bytes("no shapes") is None


FUSED_AB = ("%_fused_ab_jit.3 = f32[2,256,2048]{2,1,0} custom-call(f32[2,1,5]"
            " %p, f32[2,256,2048] %x, f32[4,2,256,2048] %h), "
            "custom_call_target=\"tpu_custom_call\"")


def _run(kernel=True, counts=True, trace=True, conf_model=None,
         fused_ab=False):
    cell = spec.load(CELL)
    m = conf_model or cell.config["model"]
    t0, t1 = 100.0, 102.0
    recs = []
    for i in range(10):
        s = traffic.Send(at_s=0.0, seq_len=100 + 10 * i, nfe=10, seed=i)
        r = C.Record(uid=i, send=s, t_due=t0 + 0.1 * i, t_sub=t0 + 0.1 * i)
        r.t_done = r.t_due + 0.5
        n = s.seq_len * m["n_layers"] * 10 if counts else None
        r.result = type("R", (), {"queue_wait_s": 0.0, "nfe": 10,
                                  "moe_assignments": n})()
        recs.append(r)
    view = None
    if trace:
        ops, mods = [], []
        for k in range(50):
            a = k * 20 * MS
            mods.append(tracing.Event("jit_run(7)", a, 16 * MS))
            if fused_ab:
                ops.append(tracing.Event("%fusion.2 = bf16[2]", a, 3.8 * MS))
                ops.append(tracing.Event(FUSED_AB, a + 3.8 * MS, 0.2 * MS))
            else:
                ops.append(tracing.Event("%fusion.2 = bf16[2]", a, 4 * MS))
            if kernel:
                ops.append(tracing.Event(KERNEL, a + 4 * MS, 12 * MS))
        host = [tracing.Event(tracing.WINDOW_SPAN, 0.0, 1000 * MS)]
        view = C.TraceView(tracing.Trace({DEV: ops}, {DEV: mods}, host),
                           0.0, 1000 * MS, t0, t0 + 1.0)
    return C.Run(cell=cell, open_loop=True, seconds=2.0, t0=t0, t1=t1,
                 setup_s=42.0, records=recs, group_steps=50.0, model=m,
                 peaks=cost.peaks("TPU v5 lite"), trace=view,
                 row_steps=[(t0 + 0.01 * k, (100, 180)) for k in range(50)])


def test_readers_of_the_expert_kernel_and_the_step():
    run = _run()
    roof = readers_moe.expert_roofline(run)
    assert roof == pytest.approx(100 * CALL_BYTES / 819e9 / 12e-3)
    assert 0 < roof <= 100
    assert readers_moe.expert_share(run) == pytest.approx(75.0)
    assert readers_moe.assignments_per_position(run) == pytest.approx(1.0)
    mfu = readers_moe.step_mfu(run)
    m = run.model
    flops = 50 * (cost_moe.row_forward_flops(m, 100, 1.0)
                  + cost_moe.row_forward_flops(m, 180, 1.0))
    assert mfu == pytest.approx(100 * flops / (50 * 16e-3) / 197e12)
    assert 0 < mfu <= 100
    for name in ("moe_expert_roofline.tail", "moe_expert_share.tail",
                 "moe_step_mfu.tail"):
        assert run.cell.reader(name)(run) is not None


def test_every_metric_listed_for_the_cell_reads_a_number():
    """The cell's traced run reads every per-layer metric whose
    ``workloads`` name it: its own three and the scheduler, fused AB kernel
    and idle shares it shares with the dense cells."""
    run = _run(fused_ab=True)
    names = [ent["name"] for ent in run.cell.per_layer]
    assert {"moe_expert_roofline.tail", "fused_ab_roofline.tail",
            "device_idle_share.tail", "queue_wait_p95_s"} <= set(names)
    assert "step_mfu.tail" not in names      # dense FLOPs: not this model
    for name in names:
        val = run.cell.reader(name)(run)
        assert isinstance(val, float) and val >= 0.0, name
        if name.endswith(("roofline.tail", "mfu.tail", "share.tail")):
            assert val <= 100.0, name


def test_readers_without_a_trace_a_kernel_or_a_count():
    for name in ("moe_expert_roofline.tail", "moe_expert_share.tail",
                 "moe_step_mfu.tail"):
        assert spec.load(CELL).reader(name)(_run(trace=False)) is None
    no_kernel = _run(kernel=False)
    assert readers_moe.expert_roofline(no_kernel) == 0.0
    assert readers_moe.expert_share(no_kernel) == 0.0
    # a program that reports no count (the parent's) reads nothing
    assert readers_moe.step_mfu(_run(counts=False)) is None
    assert readers_moe.assignments_per_position(_run(counts=False)) is None


# ------------------------------------------------- the cell's command
TINY = {"model": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "vocab_size": 256}}
# at this width, rows of 48-64 and 8 requests compared, five seeds read at
# most 2.5e-4 (program) and at least 9.9e-3 (control); the cell's own limit
# comes from the chip at its sizes
TINY_LIMIT = 2e-3


@pytest.fixture
def small_cell(monkeypatch):
    load = spec.load

    def small(name, *a, **k):
        c = load(name, *a, **k)
        c.traffic = dict(c.traffic, seq_len={"dist": "uniform", "min": 48,
                                             "max": 64}, buckets=[64],
                         lead_s=0.5, check_requests=8)
        c.limits = dict(c.limits, max_logit_gap=TINY_LIMIT)
        return c
    monkeypatch.setattr(spec, "load", small)


def test_the_served_path_passes_and_the_float8_control_fails(small_cell):
    """Through ``build_diffusion_engine`` and ``ServeDriver`` at a tiny
    width with the published experts (128, top-8, 16 held, width 768): the
    run with ``--control 1`` comes out not correct, and the program's own
    gap, printed beside it, is under the limit."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    out, err = io.StringIO(), io.StringIO()
    try:
        with redirect_stdout(out), redirect_stderr(err):
            rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 5),
                           "--seconds", "1.5", "--trace", "0", "--control",
                           "1"], rehearsal=TINY)
    finally:
        jax.config.update("jax_enable_x64", prev)
    assert rc == 0, err.getvalue()[-2000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is False and line["attempted"] > 0
    assert line["compared"]["max_logit_gap"]["value"] > TINY_LIMIT
    prog = re.search(r"program's own max_logit_gap (\S+)", err.getvalue())
    assert prog and float(prog.group(1)) <= TINY_LIMIT
    assert re.search(r"window 1\.5 s: 0 programs", err.getvalue())
