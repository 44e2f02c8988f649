"""Every metric reader under ``metrics/``, on a synthetic run: a number
where it has something to read, None where it has not, and a share of a
peak or of a roofline that never passes 100% on sound inputs."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import cell as C  # noqa: E402
from chipbench import cost, spec, tracing, traffic  # noqa: E402

METRICS = sorted(p.stem for p in (BENCH / "metrics").glob("*.py"))
DEV = "/device:TPU:0"
MS = 1e6                                  # ns


def _bench():
    return json.loads((BENCH.parents[1] / "BENCHMARK.json").read_text())


def test_every_metric_in_the_benchmark_has_a_reader():
    b = _bench()
    names = {m["name"] for m in b["end_to_end"] + b["per_layer"]}
    assert names <= set(METRICS)


def _records(t0, t1, open_loop):
    out = []
    for i in range(20):
        s = traffic.Send(at_s=0.0, seq_len=100 + i, nfe=10, seed=i)
        due = t0 + 0.1 * i
        r = C.Record(uid=i, send=s, t_due=due, t_sub=due)
        r.events = [due + 0.01 * k for k in range(10)]
        r.t_done = due + 0.5 + 0.01 * i
        r.result = type("R", (), {"queue_wait_s": 0.001 * i})()
        out.append(r)
    return out


def _trace(t_lo, t_hi, step_ns, kernel_ns):
    """A device that runs one step program of ``step_ns`` per 10 ms, with
    one fused AB kernel call of ``kernel_ns`` inside it."""
    ops, mods = [], []
    hlo = ("%_fused_ab_jit.1 = f32[8,256,3840]{2,1,0} custom-call(f32[8,1,5]"
           " %p, f32[8,256,3840] %x, f32[4,8,256,3840] %h), "
           "custom_call_target=\"tpu_custom_call\"")
    for k in range(100):
        a = k * 10 * MS
        mods.append(tracing.Event("jit_run(3)", a, step_ns))
        ops.append(tracing.Event("%fusion.1 = f32[2]", a, step_ns - kernel_ns))
        ops.append(tracing.Event(hlo, a + step_ns - kernel_ns, kernel_ns))
    host = [tracing.Event(tracing.WINDOW_SPAN, 0.0, 1000 * MS)]
    tr = tracing.Trace({DEV: ops}, {DEV: mods}, host)
    return C.TraceView(tr, 0.0, 1000 * MS, t_lo, t_hi)


@pytest.mark.parametrize("open_loop", [True, False])
@pytest.mark.parametrize("name", METRICS)
def test_reader(name, open_loop):
    cell = spec.load("danube3-4b.chat-s256")
    m = cell.config["model"]
    t0, t1 = 100.0, 102.0
    recs = _records(t0, t1, open_loop)
    step_ns, kernel_ns = 8 * MS, 0.4 * MS
    run = C.Run(cell=cell, open_loop=open_loop, seconds=t1 - t0, t0=t0,
                t1=t1, setup_s=42.0, records=recs, group_steps=50.0,
                model=m, peaks=cost.peaks("TPU v5 lite"),
                trace=_trace(t0, t0 + 1.0, step_ns, kernel_ns),
                row_steps=sorted((t, (r.send.seq_len,)) for r in recs
                                 for t in r.events))
    val = cell.reader(name)(run)
    if name.startswith(("latency_", "tokens_per_s")):
        assert (val is None) == (open_loop == name.startswith("tokens"))
    else:
        assert isinstance(val, float) and val >= 0.0
    if name.startswith(("step_mfu", "fused_ab_roofline")):
        assert 0.0 < val <= 100.0
    if name.startswith("device_idle_share"):
        assert val == pytest.approx(20.0)
    if name.startswith("fused_ab_roofline"):
        b = 8 * 5 * 4 + 8 * 256 * 3840 * 4 * 6
        assert val == pytest.approx(100 * b / 819e9 / 0.4e-3)
    if name == "rows_per_step":
        assert val == pytest.approx(200 / 50)
    no_trace = C.Run(cell=cell, open_loop=open_loop, seconds=t1 - t0, t0=t0,
                     t1=t1, setup_s=42.0, records=[], group_steps=0.0,
                     model=m, peaks=cost.peaks("TPU v5 lite"))
    if name != "setup_s":
        assert cell.reader(name)(no_trace) is None
