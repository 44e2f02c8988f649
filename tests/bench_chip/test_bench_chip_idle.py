"""The split of device idle time by program span (``chipbench.idle``), on
synthetic traces: the classes add up to ``device_idle_share``, the
innermost open span names an instant, and host events that are not
program spans are ignored."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import cell as C  # noqa: E402
from chipbench import idle, readers, tracing  # noqa: E402

MS = 1e6                                  # ns


def _run(ops: dict, host: list, lo=0.0, hi=100 * MS):
    tr = tracing.Trace({d: [tracing.Event("%op", a * MS, (b - a) * MS)
                            for a, b in evs] for d, evs in ops.items()},
                       {}, [tracing.Event(n, a * MS, (b - a) * MS)
                            for n, a, b in host])
    view = C.TraceView(tr, lo, hi, 0.0, (hi - lo) * 1e-9)
    return C.Run(cell=None, open_loop=True, seconds=1.0, t0=0.0, t1=1.0,
                 setup_s=1.0, records=[], group_steps=0.0, model={},
                 peaks={}, trace=view)


def test_classes_sum_to_the_idle_share_over_devices():
    ops = {"/device:TPU:0": [(5, 20), (30, 31), (31, 60), (90, 99)],
           "/device:TPU:1": [(0, 50), (70, 100)]}
    host = [("admit", 0, 12), ("admit.form.prior", 3, 9),
            ("dispatch", 12, 14), ("step_wait", 14, 24), ("decode", 24, 26),
            ("fanout", 26, 27), ("resolve", 27, 28), ("idle", 40, 65),
            ("inbox", 65, 66), ("compile", 80, 85)]
    run = _run(ops, host)
    got = idle.shares(run)
    assert set(got) == {"admit", "step", "handoff", "wait", "untraced"}
    assert sum(got.values()) == pytest.approx(readers.device_idle_share(run),
                                              abs=1e-9)
    # device 0 idle: 0-5 admit; 20-30: step 20-24, handoff 24-28, none
    # 28-30; 60-90: wait 60-65, handoff 65-66, none 66-80, compile 80-85
    # (admit), none 85-90; none 99-100. Device 1 idle, 50-70: wait 50-65,
    # handoff 65-66, none 66-70.
    assert got["admit"] == pytest.approx(100 * (5 + 5) / 200)
    assert got["step"] == pytest.approx(100 * 4 / 200)
    assert got["handoff"] == pytest.approx(100 * (4 + 1 + 1) / 200)
    assert got["wait"] == pytest.approx(100 * (5 + 15) / 200)
    assert got["untraced"] == pytest.approx(
        100 * (2 + 14 + 5 + 1 + 4) / 200)


def test_the_innermost_open_span_names_the_instant():
    """A span opened inside another takes its stretch, and the outer one
    takes the rest back when it closes; the latest-started wins."""
    host = [("admit", 0, 50), ("decode", 10, 20), ("admit.join", 30, 40)]
    segs = idle.segments([tracing.Event(n, a, b - a) for n, a, b in host],
                         0.0, 60.0)
    assert segs == [(0.0, 10.0, "admit"), (10.0, 20.0, "handoff"),
                    (20.0, 50.0, "admit"), (50.0, 60.0, "untraced")]
    run = _run({"/device:TPU:0": [(0, 10), (20, 100)]},
               [("idle", 0, 100), ("dispatch", 12, 15)])
    got = idle.shares(run)
    assert got["step"] == pytest.approx(3.0)
    assert got["wait"] == pytest.approx(7.0)


def test_host_events_that_are_not_program_spans_are_ignored():
    host = [("bench.window", 0, 100), ("PjitFunction(jit(run))", 0, 100),
            ("tick", 0, 100), ("admitted", 0, 100), ("admit", 40, 50)]
    run = _run({"/device:TPU:0": [(0, 30)]}, host)
    got = idle.shares(run)
    assert got["admit"] == pytest.approx(10.0)
    assert got["untraced"] == pytest.approx(60.0)
    assert idle.class_of("admit.form.prior") == "admit"
    assert idle.class_of("tick.admit") is None


@pytest.mark.parametrize("metric", ["admit_idle_share.tail",
                                    "handoff_idle_share.tail",
                                    "untraced_idle_share.tail"])
def test_readers_find_nothing_without_a_trace_or_device_op(metric):
    from chipbench import spec
    read = spec.load("danube3-4b.chat-s256").reader(metric)
    assert read(_run({}, [("admit", 0, 10)])) is None
    run = _run({}, [])
    run.trace = None
    assert read(run) is None
    assert read(_run({"/device:TPU:0": [(0, 50)]}, [])) >= 0.0
