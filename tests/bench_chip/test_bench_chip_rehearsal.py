"""The benchmark's command, rehearsed end to end on the CPU at a tiny
model size (interpret-mode kernels), through its test-only rehearsal hook.
"""
import io
import json
import pathlib
import re
import sys
from contextlib import redirect_stderr, redirect_stdout

import jax
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

TINY = {"model": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 256}}
CELL = "danube3-4b.chat-s256"
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _main(argv, **kw):
    """run.main with its output captured, and without the suite's x64 (the
    benchmark runs without it, on every thread it starts)."""
    out, err = io.StringIO(), io.StringIO()
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        with redirect_stdout(out), redirect_stderr(err):
            rc = run.main(argv, **kw)
    finally:
        jax.config.update("jax_enable_x64", prev)
    return rc, out.getvalue(), err.getvalue()


@pytest.fixture(scope="module", params=[0, 1], ids=["trace0", "trace1"])
def rehearsal(request):
    """The chat cell with its buckets and arrivals, its lengths cut to
    100-140 (both buckets) so the CPU warms them up in a minute."""
    from chipbench import spec
    load = spec.load

    def often(name, *a, **k):
        c = load(name, *a, **k)
        c.traffic = dict(c.traffic, seq_len={"dist": "uniform", "min": 100,
                                             "max": 140})
        return c
    mp = pytest.MonkeyPatch()
    mp.setattr(spec, "load", often)
    try:
        rc, out, err = _main(["--workload", CELL, "--seed", str(2 ** 31 + 12),
                              "--seconds", "1.5", "--trace",
                              str(request.param)], rehearsal=TINY)
    finally:
        mp.undo()
    return request.param, rc, out, err


def test_last_line_has_exactly_the_contract_keys(rehearsal):
    trace, rc, out, err = rehearsal
    assert rc == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    want = KEYS + (["breakdown"] if trace else []) + ["compared"]
    assert list(line) == want
    assert line["correct"] is True, err[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {"latency_p50_s", "latency_p95_s",
                                        "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    cmp = line["compared"]["max_logit_gap"]
    assert set(cmp) == {"value", "limit"}
    assert re.search(r"compared max_logit_gap \S+ limit \S+\s*$", err)


def test_warm_up_covers_every_shape_the_traffic_draws(rehearsal):
    """No program compiles or loads inside the window."""
    _trace, rc, _out, err = rehearsal
    assert rc == 0
    m = re.search(r"window 1\.5 s: (\d+) programs", err)
    assert m and int(m.group(1)) == 0, err[-2000:]


def test_without_a_tpu_it_exits_before_building_anything(monkeypatch):
    from chipbench import cell

    def boom(*a, **k):
        raise AssertionError("built something")
    monkeypatch.setattr(cell, "run_cell", boom)
    monkeypatch.setattr(cell, "prepare", boom)
    rc, out, err = _main(["--workload", CELL, "--seed", "1", "--seconds",
                          "1", "--trace", "0"])
    assert rc != 0
    assert out == ""
    assert "no TPU" in err


def test_unknown_workload_raises():
    with pytest.raises(KeyError):
        _main(["--workload", "nope", "--seed", "1", "--seconds", "1"])
