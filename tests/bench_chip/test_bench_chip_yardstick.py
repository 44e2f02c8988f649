"""The benchmark's yardstick on the CPU: operation and byte counts, the
peaks table, the traffic generator, the reference solver's coefficients
and the trace reduction (on synthetic events and on a small trace
recorded on a v5e)."""
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import cost, deis_ref, tracing, traffic  # noqa: E402

FIXTURE = pathlib.Path(__file__).resolve().parent / "v5e_tiny.xplane.pb"


def _model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_danube_row_flops_match_the_arithmetic():
    """About 1.9 TFLOP per seq-256 danube row (PERF.md section 5)."""
    m = _model("danube3-4b")["model"]
    f = cost.row_forward_flops(m, 256)
    assert 1.85e12 < f < 2.0e12
    # the MLP is three 2*L*d*f products per layer
    assert cost.layer_flops(m, 256) > 6 * 256 * 3840 * 10240


def test_attention_flops_grow_with_the_square_of_length():
    m = _model("granite3-8b-l20")["model"]
    a = cost.layer_flops(m, 1024) - 2 * cost.layer_flops(m, 512)
    # the two quadratic terms: 4 * L^2 * q_dim more at 1024 than 2x512
    assert a == pytest.approx(4 * (1024 ** 2 - 2 * 512 ** 2) * 32 * 128)


@pytest.mark.parametrize("name,n_bytes", [("danube3-4b", 7_984_642_560),
                                          ("granite3-8b-l20", 8_844_091_392)])
def test_param_count_matches_the_program_layout(name, n_bytes):
    """7.98 GB of bf16 danube params (PERF.md), and the program's own
    layout for both configurations."""
    from chipbench import model
    conf = _model(name)
    assert 2 * cost.param_count(conf["model"]) == n_bytes
    cfg = model.program_config(conf)
    layout = model.param_layout(cfg)
    assert sum(a.size for a in jax.tree.leaves(layout)) \
        == cost.param_count(conf["model"])


def test_peaks_lookup():
    p = cost.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cost.peaks("TPU v99")


def test_fused_ab_bytes_from_custom_call_text():
    text = ("%_fused_ab_jit.1 = f32[8,256,3840]{2,1,0:T(8,128)} custom-call("
            "f32[8,1,5]{2,1,0:T(1,128)S(1)} %a, f32[8,256,3840]{2,1,0} %x, "
            "f32[4,8,256,3840]{3,2,1,0} %h), custom_call_target="
            "\"tpu_custom_call\", operand_layout_constraints={f32[8,1,5]"
            "{2,1,0}, f32[8,256,3840]{2,1,0}, f32[4,8,256,3840]{3,2,1,0}}")
    row = 8 * 256 * 3840 * 4
    # the scalars sit in VMEM (S(1)): no HBM read of theirs in the call
    assert cost.fused_ab_bytes(text) == row * (1 + 1 + 4)
    assert cost.fused_ab_bytes("no shapes here") is None


@pytest.mark.parametrize("mix", ["chat-s256", "batch-s1024", "burst-s32"])
def test_every_seed_sends_the_same_work(mix):
    tr = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    a = traffic.schedule(tr, 2 ** 31 + 7, 40.0)
    b = traffic.schedule(tr, 11, 40.0)
    assert sorted((s.seq_len, s.nfe) for s in a) \
        == sorted((s.seq_len, s.nfe) for s in b)
    if "schedule_seed" in tr:     # one replayed schedule, own request seeds
        assert [(s.at_s, s.seq_len) for s in a] \
            == [(s.at_s, s.seq_len) for s in b]
        assert [s.seed for s in a] != [s.seed for s in b]
    else:
        assert [s.seq_len for s in a] != [s.seq_len for s in b]
    lens = set(traffic.lengths(tr, traffic.n_requests(tr, 40.0)))
    assert {s.seq_len for s in a} <= lens
    assert all(tr["seq_len"]["min"] <= n <= tr["seq_len"]["max"]
               for n in lens)
    if tr["loop"] == "open":
        gaps = np.diff(sorted({s.at_s for s in a}))
        burst = tr["arrival"].get("burst", 1)
        assert len(a) % burst == 0
        assert np.mean(gaps) == pytest.approx(
            burst / tr["arrival"]["rate_per_s"], rel=0.1)


@pytest.mark.parametrize("nfe", [5, 10, 20])
def test_reference_coefficients_match_the_program(nfe):
    """The reference's tAB3 coefficients (Gauss-Legendre in t) agree with
    the program's plan (quadrature in rho) to round-off."""
    from repro.core import get_timesteps, make_plan
    from repro.core.sde import VPSDE
    diff = _model("danube3-4b")["diffusion"]
    ts = deis_ref.timesteps(diff, nfe)
    sde = VPSDE()
    np.testing.assert_allclose(ts, get_timesteps(sde, nfe, "quadratic"),
                               rtol=1e-12)
    psi, C = deis_ref.tab_coefficients(diff, ts, 3)
    plan = make_plan("tab3", sde, ts)
    np.testing.assert_allclose(psi, np.asarray(plan.coeffs["psi"]),
                               rtol=1e-10)
    np.testing.assert_allclose(C, np.asarray(plan.coeffs["C"]), rtol=1e-7,
                               atol=1e-10)


def _ev(name, a, b):
    return tracing.Event(name, float(a), float(b - a))


def test_busy_union_and_gaps():
    evs = [_ev("x", 0, 10), _ev("y", 5, 20), _ev("z", 30, 40),
           _ev("w", 95, 120)]
    assert tracing.merge([(e.start_ns, e.end_ns) for e in evs], 0, 100) \
        == [(0, 20), (30, 40), (95, 100)]
    assert tracing.busy_ns(evs, 0, 100) == 35
    assert tracing.gaps(evs, 0, 100) == [(20, 30), (40, 95)]
    assert tracing.top_ops(evs, 0, 100, 2) == [("y", 15.0), ("x", 10.0)]


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    spans = [_ev("tick", 0, 100), _ev("admit", 18, 35), _ev("other", 40, 90)]
    names = {"admit", "tick"}
    assert tracing.attribute((20, 30), spans, names) == "admit"
    assert tracing.attribute((40, 95), spans, names) == "tick"
    assert tracing.attribute((120, 130), spans, names) == "unattributed"


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.is_file():
        pytest.fail(f"missing {FIXTURE.name}")
    return tracing.read(str(FIXTURE))


def test_recorded_trace_has_a_device_the_window_and_the_kernel(recorded):
    """A trace of a tiny served group on one v5e: the reduction finds the
    device plane, the benchmark's window span, the step programs and the
    fused AB kernel with its operand shapes."""
    from chipbench import readers
    assert list(recorded.device_ops) == ["/device:TPU:0"]
    lo, hi = tracing.window(recorded)
    assert hi > lo
    ops = recorded.device_ops["/device:TPU:0"]
    busy = tracing.busy_ns(ops, lo, hi)
    assert 0 < busy <= hi - lo
    kernels = [e for e in ops if readers.FUSED_AB.search(e.name)]
    assert kernels
    assert all(cost.fused_ab_bytes(e.name) for e in kernels)
    mods = recorded.device_modules["/device:TPU:0"]
    assert any(readers.STEP_MODULE.search(e.name) for e in mods)
    idle = tracing.gaps(ops, lo, hi)
    assert sum(b - a for a, b in idle) == pytest.approx(hi - lo - busy)
