"""Unit tests for the observability subsystem (repro.obs).

Pure host-side tests: registry/histogram semantics, the Prometheus and
NDJSON renderers, span nesting, and the BENCH ratchet -- no jax arrays, no
engine. The serving integration (engine counters, driver stats, deadline
eviction accounting) lives in test_serving_fuzz.py / test_driver.py.
"""
import json
import threading

import pytest

from repro.obs import MetricsRegistry, Tracer, NULL_TRACER
from repro.obs import bench
from repro.obs.export import NdjsonExporter, to_ndjson_line, to_prometheus
from repro.obs.metrics import Counter, Gauge, Histogram


# ------------------------------------------------------------------ metrics
def test_counter_inc_and_reset():
    c = Counter("requests_total", "help")
    assert c.value == 0.0
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    c.reset()
    assert c.value == 0.0
    c.reset(7)
    assert c.value == 7.0


def test_gauge_set_and_inc():
    g = Gauge("depth", "help")
    g.set(4)
    g.inc(-1)
    assert g.value == 3.0


def test_histogram_bucket_placement():
    h = Histogram("lat", "help", edges=(1.0, 10.0))
    for v in (0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    # buckets: (-inf, 1], (1, 10], (10, inf) with bisect_left semantics:
    # an observation equal to an edge lands in that edge's bucket
    assert h.counts == [2, 1, 1]
    assert h.cumulative() == [2, 3, 4]
    assert h.count == 4
    assert h.sum == pytest.approx(106.5)
    h.reset()
    assert h.count == 0 and h.sum == 0.0 and h.counts == [0, 0, 0]


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram("h", "help", edges=(1.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("h", "help", edges=())


def test_registry_idempotent_and_type_checked():
    reg = MetricsRegistry()
    c1 = reg.counter("a_total", help="x")
    c2 = reg.counter("a_total", help="ignored on re-register")
    assert c1 is c2
    with pytest.raises(ValueError):
        reg.gauge("a_total", help="wrong kind under the same name")
    assert "a_total" in reg
    assert reg.get("missing") is None


def test_registry_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c_total", help="c").inc(2)
    reg.gauge("g", help="g").set(1.5)
    reg.histogram("h_seconds", help="h", edges=(0.1, 1.0)).observe(0.05)
    snap = reg.snapshot()
    assert snap["c_total"] == 2.0
    assert snap["g"] == 1.5
    assert snap["h_seconds"] == {"edges": [0.1, 1.0], "counts": [1, 0, 0],
                                 "sum": 0.05, "count": 1}
    # a snapshot is a plain-data copy: mutating it must not touch the metric
    snap["h_seconds"]["counts"][0] = 99
    assert reg.get("h_seconds").counts[0] == 1


def test_registry_single_writer_multi_reader():
    """Concurrent reads (scrape threads) during writes never error and the
    final totals are exact -- the registry's documented threading model."""
    reg = MetricsRegistry()
    c = reg.counter("n_total", help="n")
    stop = threading.Event()
    errs = []

    def scrape():
        while not stop.is_set():
            try:
                to_prometheus(reg)
                reg.snapshot()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

    t = threading.Thread(target=scrape)
    t.start()
    for _ in range(20000):
        c.inc()
    stop.set()
    t.join()
    assert not errs
    assert c.value == 20000


# ------------------------------------------------------------------- export
def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("served_total", help="requests served").inc(3)
    reg.gauge("queue_depth", help="pending").set(2)
    h = reg.histogram("solve_seconds", help="solve", edges=(0.5, 2.0))
    h.observe(0.1)
    h.observe(1.0)
    text = to_prometheus(reg)
    assert "# TYPE served_total counter" in text
    assert "served_total 3" in text
    assert "# TYPE queue_depth gauge" in text
    assert 'solve_seconds_bucket{le="0.5"} 1' in text
    assert 'solve_seconds_bucket{le="2"} 2' in text
    assert 'solve_seconds_bucket{le="+Inf"} 2' in text
    assert "solve_seconds_sum 1.1" in text
    assert "solve_seconds_count 2" in text
    assert text.endswith("\n")


def test_prometheus_names_are_valid_for_nested_spans():
    """A nested span's histogram (``trace_admit.form_seconds``) and any other
    registry name render as valid Prometheus names; the registry keeps its
    own names."""
    import re
    reg = MetricsRegistry()
    tr = Tracer(reg)
    with tr.span("admit"):
        with tr.span("form"):
            pass
    reg.counter("9lives-total").inc()
    reg.gauge("queue depth").set(2)
    text = to_prometheus(reg)
    valid = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*")
    names = []
    for line in text.splitlines():
        if line.startswith("#"):
            names.append(line.split()[2])
        else:
            names.append(re.split(r"[{ ]", line, maxsplit=1)[0])
    assert names and all(valid.fullmatch(n) for n in names), names
    assert "trace_admit_form_seconds_count 1" in text
    assert "_9lives_total 1" in text
    assert "trace_admit.form_seconds" in reg


def test_ndjson_line_and_exporter(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c_total", help="c").inc()
    doc = json.loads(to_ndjson_line(reg, extra={"run": "t"}))
    assert doc["metrics"]["c_total"] == 1.0
    assert doc["run"] == "t"
    assert doc["ts"] > 0

    path = tmp_path / "metrics.ndjson"
    with NdjsonExporter(str(path)) as ex:
        ex.write(reg)
        reg.get("c_total").inc()
        ex.write(reg)
    lines = path.read_text().splitlines()
    assert [json.loads(l)["metrics"]["c_total"] for l in lines] == [1.0, 2.0]


# -------------------------------------------------------------------- trace
def test_tracer_nested_spans_record_dotted_paths():
    reg = MetricsRegistry()
    tr = Tracer(reg)
    with tr.span("tick"):
        with tr.span("admit"):
            pass
        with tr.span("dispatch"):
            pass
    with tr.span("tick"):
        pass
    assert tr.span_names() == ["tick", "tick.admit", "tick.dispatch"]
    assert reg.get("trace_tick_seconds").count == 2
    assert reg.get("trace_tick.admit_seconds").count == 1


def test_tracer_stack_unwinds_after_exception():
    tr = Tracer(MetricsRegistry())
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise RuntimeError("boom")
    with tr.span("after"):
        pass
    assert "after" in tr.span_names()          # not "outer.after"


def test_null_tracer_records_nothing():
    with NULL_TRACER.span("anything"):
        pass
    assert NULL_TRACER.span_names() == []


def test_tracer_registers_each_histogram_once():
    reg = MetricsRegistry()
    calls = []
    register = reg.histogram

    def counted(name, *a, **kw):
        calls.append(name)
        return register(name, *a, **kw)
    reg.histogram = counted
    tr = Tracer(reg)
    for _ in range(3):
        with tr.span("admit"):
            with tr.span("form"):
                pass
    assert sorted(calls) == ["trace_admit.form_seconds", "trace_admit_seconds"]
    assert reg.get("trace_admit.form_seconds").count == 3


@pytest.mark.parametrize("annotate", [False, True])
def test_span_args_reach_the_annotation_only_when_annotating(annotate,
                                                             monkeypatch):
    from repro.obs import trace as obs_trace
    made = []

    class Annotation:
        def __init__(self, name, **args):
            made.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Annotation)
    tr = Tracer(MetricsRegistry(), annotate=annotate)
    with tr.span("step_wait", rows=3, slots=4, seq=256):
        with tr.span("inner"):
            pass
    want = [("step_wait", {"rows": 3, "slots": 4, "seq": 256}),
            ("step_wait.inner", {})]
    assert made == (want if annotate else [])
    assert tr.span_names() == ["step_wait", "step_wait.inner"]


# -------------------------------------------------------------------- bench
def _rec(metrics):
    return bench.record("t", metrics, {"quick": True})


def test_bench_metric_validates_direction():
    with pytest.raises(ValueError):
        bench.metric(1.0, direction="sideways")


def test_bench_write_load_roundtrip(tmp_path):
    p = tmp_path / "BENCH_t.json"
    rec = _rec({"m": bench.metric(1.0, unit="us", ratchet=True, tol=0.0)})
    bench.write(str(p), rec)
    assert bench.load(str(p))["metrics"] == rec["metrics"]
    p.write_text('{"schema": "bench.v0"}')
    with pytest.raises(ValueError):
        bench.load(str(p))


def test_bench_self_compare_is_clean():
    rec = _rec({"m": bench.metric(3.0, ratchet=True, tol=0.0),
                "z": bench.metric(0.0, ratchet=True, tol=0.0)})
    assert bench.regressions(bench.compare(rec, rec)) == []


def test_bench_ratchet_directions_and_tol():
    base = _rec({
        "wasted": bench.metric(0.0, direction="lower", ratchet=True, tol=0.0),
        "joined": bench.metric(4.0, direction="higher", ratchet=True, tol=0.0),
        "lat": bench.metric(100.0, direction="lower", ratchet=True, tol=0.1),
        "info": bench.metric(100.0, direction="lower", ratchet=False),
    })
    cur = _rec({
        "wasted": bench.metric(1.0),     # worse (lower is better)
        "joined": bench.metric(3.0),     # worse (higher is better)
        "lat": bench.metric(109.0),      # within 10% tol
        "info": bench.metric(500.0),     # worse but not ratcheted
    })
    by_name = {c.name: c for c in bench.compare(base, cur)}
    assert by_name["wasted"].regressed
    assert by_name["joined"].regressed
    assert not by_name["lat"].regressed
    assert not by_name["info"].regressed
    cur2 = _rec({"lat": bench.metric(111.0)})   # past the 10% tol
    assert bench.compare(base, cur2)[0].regressed


def test_bench_new_and_dropped_metrics_do_not_fail():
    base = _rec({"old": bench.metric(1.0, ratchet=True, tol=0.0)})
    cur = _rec({"new": bench.metric(9.0, ratchet=True, tol=0.0)})
    assert bench.compare(base, cur) == []      # no shared metrics


def test_bench_cli_compare(tmp_path, capsys):
    pb, pc = str(tmp_path / "b.json"), str(tmp_path / "c.json")
    bench.write(pb, _rec({"m": bench.metric(1.0, ratchet=True, tol=0.0)}))
    bench.write(pc, _rec({"m": bench.metric(1.0, ratchet=True, tol=0.0)}))
    assert bench.main(["compare", pb, pc]) == 0
    assert "ratchet clean" in capsys.readouterr().out
    bench.write(pc, _rec({"m": bench.metric(2.0)}))
    assert bench.main(["compare", pb, pc]) == 1
    assert bench.main(["show", pb]) == 0
