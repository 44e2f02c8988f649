"""Program spans of the served path: the engine's and the driver's spans
cover the scheduler thread, ``step_wait`` carries its group's shape on the
profiler annotation, and every recorded path falls in one of the classes
the on-chip benchmark splits device idle time by."""
import pathlib
import sys

import jax
import pytest

from repro.configs.base import get_config
from repro.core.adaptive import RetirePolicy
from repro.models import transformer as T
from repro.obs import trace as obs_trace
from repro.serving.driver import ServeDriver
from repro.serving.engine import DiffusionServeEngine, Request

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))
from chipbench import idle  # noqa: E402

SPANS = {"admit", "admit.evict", "admit.retire",
         "admit.form", "admit.form.prior", "admit.form.compile",
         "admit.join", "admit.join.prior", "admit.join.compile",
         "admit.compact", "admit.compact.compile",
         "dispatch", "step_wait", "decode", "fanout",
         "inbox", "idle", "resolve"}


@pytest.fixture(scope="module")
def diff_setup():
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


class _Scripted(ServeDriver):
    """A driver that submits more requests from its own fan-out, when a
    request reaches a given step: they are drained before the next tick,
    so which tick admits them does not depend on thread timing."""

    def __init__(self, engine, script):
        super().__init__(engine)
        self.script = script            # (uid, own k) -> [Request]
        self.handles = []

    def _fanout(self, event):
        super()._fanout(event)
        for uid, k in zip(event.uids, event.row_k):
            for req in self.script.pop((uid, k), []):
                self.handles.append(self.submit(req))


def _req(uid, nfe, seq_len=16):
    return Request(uid=uid, seq_len=seq_len, nfe=nfe, solver="ddim",
                   seed=uid)


@pytest.fixture(scope="module")
def served(diff_setup):
    """Tick 1 steps a lone starter; its first step brings two requests
    that form a group at tick 2; the shorter one's finish (tick 3) brings
    two joiners (tick 4, a 3-row executor); their finish (tick 5) leaves
    the long row alone, so tick 6 compacts it to one row."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg, enforce_deadlines=True,
                               retire=RetirePolicy(tol=1e-3))
    drv = _Scripted(eng, {(0, 1): [_req(1, 2), _req(2, 6)],
                          (1, 2): [_req(3, 2), _req(4, 2)]})
    with drv:
        drv.handles.append(drv.submit(_req(0, 2, seq_len=8)))
        for _ in range(3):      # the starter, the long row, the last joiner
            drv.handles[-1].result(timeout=300)
        results = [h.result(timeout=300) for h in drv.handles]
    return eng, results


def test_served_path_records_exactly_the_span_table(served):
    eng, results = served
    assert [r.nfe for r in results] == [2, 2, 6, 2, 2]
    assert eng.joined_requests == 2
    assert eng.metrics.get("serve_compactions_total").value == 1
    assert set(eng.tracer.span_names()) == SPANS


def test_every_recorded_span_has_an_idle_class(served):
    eng, _ = served
    for path in eng.tracer.span_names():
        assert idle.class_of(path) is not None, path
    assert idle.class_of("tick.admit") is None


def test_step_wait_carries_its_group_shape(diff_setup, monkeypatch):
    params, cfg = diff_setup
    seen = []

    class Annotation:
        def __init__(self, name, **args):
            seen.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Annotation)
    eng = DiffusionServeEngine(params, cfg, seq_len_buckets=(16,))
    eng.tracer = obs_trace.Tracer(eng.metrics, annotate=True)
    eng.serve([_req(10, 2, seq_len=12), _req(11, 3, seq_len=16)])
    waits = [args for name, args in seen if name == "step_wait"]
    assert waits == [{"rows": 2, "slots": 2, "seq": 16}] * 2 \
        + [{"rows": 1, "slots": 1, "seq": 16}]
    assert all(args == {} for name, args in seen if name != "step_wait")
