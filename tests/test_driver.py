"""Async ServeDriver transport contracts: threaded submit with per-request
event streams and futures, asyncio submission, per-request validation-error
delivery, bitwise parity with the synchronous engine, and the HTTP-ish
NDJSON transport in ``repro.launch.serve``."""
import asyncio
import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.launch.serve import make_http_server
from repro.models import transformer as T
from repro.serving.driver import QueueFull, ServeDriver
from repro.serving.engine import DiffusionServeEngine, Request


@pytest.fixture(scope="module")
def diff_setup():
    cfg = get_config("gemma_2b").reduced().with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def test_driver_streams_and_matches_sync_engine(diff_setup):
    """Concurrent submits through the driver produce per-request event
    streams with the request's OWN progress (even in a ragged group) and
    final samples bitwise-equal to a synchronous solo serve."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    with ServeDriver(eng) as drv:
        h1 = drv.submit(Request(uid=0, seq_len=8, nfe=3, solver="ddim", seed=1))
        h2 = drv.submit(Request(uid=1, seq_len=8, nfe=6, solver="ddim", seed=2))
        evs = list(h1.events())
        assert [e.k for e in evs] == [1, 2, 3]          # own step count, not
        assert all(e.n_steps == 3 and e.uids == (0,) for e in evs)  # group max
        r1, r2 = h1.result(), h2.result()
    assert (r1.nfe, r2.nfe) == (3, 6)
    sync = DiffusionServeEngine(params, cfg)
    s1 = sync.serve([Request(uid=0, seq_len=8, nfe=3, solver="ddim", seed=1)])
    s2 = sync.serve([Request(uid=1, seq_len=8, nfe=6, solver="ddim", seed=2)])
    np.testing.assert_array_equal(r1.tokens, s1[0].tokens)
    np.testing.assert_array_equal(r2.tokens, s2[0].tokens)


def test_driver_async_submission(diff_setup):
    """submit_async handles support ``async for`` event iteration and
    awaitable results on an asyncio loop while the scheduler thread runs."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)

    async def go(drv):
        h = await drv.submit_async(
            Request(uid=7, seq_len=8, nfe=4, solver="euler", seed=3))
        ks = [ev.k async for ev in h]
        return ks, await h.result()

    with ServeDriver(eng) as drv:
        ks, res = asyncio.run(go(drv))
    assert ks == [1, 2, 3, 4] and res.nfe == 4 and res.tokens.shape == (8,)


def test_driver_validation_error_is_per_request(diff_setup):
    """A bad request fails on ITS handle (the engine's validation exception,
    delivered through the future); concurrent good requests are unaffected
    -- unlike the synchronous serve()'s all-or-nothing batch contract."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    with ServeDriver(eng) as drv:
        good = drv.submit(Request(uid=0, seq_len=8, nfe=3, solver="ddim",
                                  seed=0))
        bad = drv.submit(Request(uid=1, seq_len=8, nfe=3, solver="nope"))
        with pytest.raises(ValueError, match="unknown solver"):
            bad.result(timeout=30)
        assert list(bad.events()) == []               # stream closed, empty
        assert good.result().tokens.shape == (8,)
        with pytest.raises(ValueError, match="eta"):
            drv.submit(Request(uid=2, seq_len=8, nfe=3,
                               solver="ddim_eta")).result(timeout=30)


def test_driver_survives_tick_crash(diff_setup):
    """If a tick raises, the scheduler thread must not die silently: every
    in-flight future fails with the error, the engine queues are reset, and
    the driver keeps serving later submissions."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    real_tick = eng.tick
    boom = {"armed": True}

    def exploding_tick(**kw):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("device fell over")
        return real_tick(**kw)

    eng.tick = exploding_tick
    with ServeDriver(eng) as drv:
        h = drv.submit(Request(uid=0, seq_len=8, nfe=3, solver="ddim", seed=0))
        with pytest.raises(RuntimeError, match="fell over"):
            h.result(timeout=60)
        assert list(h.events()) == []                 # stream closed
        # driver still alive and serving
        h2 = drv.submit(Request(uid=1, seq_len=8, nfe=3, solver="ddim", seed=0))
        assert h2.result(timeout=120).tokens.shape == (8,)


def test_driver_rejects_duplicate_inflight_uid(diff_setup):
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    with ServeDriver(eng) as drv:
        h = drv.submit(Request(uid=5, seq_len=8, nfe=3, solver="ddim", seed=0))
        with pytest.raises(ValueError, match="already"):
            drv.submit(Request(uid=5, seq_len=8, nfe=3, solver="ddim", seed=1))
        h.result()
        # uid is reusable once the request completed
        drv.submit(Request(uid=5, seq_len=8, nfe=3, solver="ddim",
                           seed=1)).result()


def test_driver_backpressure_sheds_over_max_pending(diff_setup):
    """With max_pending=n the (n+1)-th concurrent submit is shed instantly:
    its OWN handle fails with QueueFull (empty event stream, no driver
    crash), every admitted request completes untouched, and capacity freed
    by completions is reusable."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    with ServeDriver(eng, max_pending=2) as drv:
        h1 = drv.submit(Request(uid=0, seq_len=8, nfe=3, solver="ddim", seed=1))
        h2 = drv.submit(Request(uid=1, seq_len=8, nfe=3, solver="ddim", seed=2))
        shed = drv.submit(Request(uid=2, seq_len=8, nfe=3, solver="ddim",
                                  seed=3))
        assert shed.done()                       # rejected at submit, O(1)
        with pytest.raises(QueueFull, match="max_pending"):
            shed.result(timeout=1)
        assert list(shed.events()) == []         # stream closed, empty
        r1, r2 = h1.result(), h2.result()        # admitted work unaffected
        assert r1.tokens.shape == (8,) and r2.tokens.shape == (8,)
        # completions free capacity; the same uid may come back
        again = drv.submit(Request(uid=2, seq_len=8, nfe=3, solver="ddim",
                                   seed=3))
        assert again.result(timeout=120).tokens.shape == (8,)
        # the shed request's sample is what a non-shed run produces
        sync = DiffusionServeEngine(params, cfg)
        want = sync.serve([Request(uid=2, seq_len=8, nfe=3, solver="ddim",
                                   seed=3)])[0]
        np.testing.assert_array_equal(again.result().tokens, want.tokens)


def test_driver_backpressure_async_path(diff_setup):
    """submit_async sheds identically: the async handle's result() raises
    QueueFull and its async iterator is empty."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)

    async def go(drv):
        h1 = await drv.submit_async(
            Request(uid=0, seq_len=8, nfe=4, solver="ddim", seed=0))
        shed = await drv.submit_async(
            Request(uid=1, seq_len=8, nfe=4, solver="ddim", seed=1))
        assert shed.done()
        evs = [ev async for ev in shed]
        with pytest.raises(QueueFull, match="shed"):
            await shed.result()
        res = await h1.result()
        return evs, res

    with ServeDriver(eng, max_pending=1) as drv:
        evs, res = asyncio.run(go(drv))
    assert evs == [] and res.tokens.shape == (8,)


def test_http_transport_roundtrip(diff_setup):
    """POST /v1/generate against the HTTP-ish transport: non-streaming JSON
    result (bitwise-equal to the driver path) and NDJSON streaming with one
    step line per solver step followed by the result line."""
    params, cfg = diff_setup
    eng = DiffusionServeEngine(params, cfg)
    with ServeDriver(eng) as drv:
        server = make_http_server(drv, 0)           # port 0: OS-assigned
        port = server.server_address[1]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            url = f"http://127.0.0.1:{port}/v1/generate"
            body = {"seq_len": 8, "nfe": 3, "solver": "ddim", "seed": 1}
            out = json.loads(urllib.request.urlopen(
                urllib.request.Request(url, data=json.dumps(body).encode()),
                timeout=120).read())
            assert out["nfe"] == 3 and len(out["tokens"]) == 8

            lines = urllib.request.urlopen(
                urllib.request.Request(url, data=json.dumps(
                    {**body, "stream": True}).encode()),
                timeout=120).read().decode().strip().split("\n")
            objs = [json.loads(ln) for ln in lines]
            assert [o["event"] for o in objs] == ["step"] * 3 + ["result"]
            assert [o["k"] for o in objs[:-1]] == [1, 2, 3]
            assert objs[-1]["tokens"] == out["tokens"]   # same seed, same sample

            # engine-side validation surfaces as NDJSON error event
            lines = urllib.request.urlopen(
                urllib.request.Request(url, data=json.dumps(
                    {**body, "solver": "nope", "stream": True}).encode()),
                timeout=120).read().decode().strip().split("\n")
            assert json.loads(lines[-1])["event"] == "error"
        finally:
            server.shutdown()


def test_compile_cache_dir_prefers_env_then_checkout(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set; without
    it the cache sits at a fixed ``.jax_cache/`` in the checkout."""
    import pathlib
    from repro.launch import serve
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = serve.enable_compile_cache()
        assert path == str(pathlib.Path(__file__).resolve().parents[1]
                           / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # outside a source checkout (an installed package) it refuses
        monkeypatch.setattr(serve, "__file__",
                            str(tmp_path / "a" / "repro" / "launch" / "s.py"))
        with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
            serve.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cli_builds_the_engine_its_options_describe():
    """The serving CLI's constructors (shared with ``chip_smoke.py``):
    ``--seed`` seeds the params, the engine compiles its ``ab`` executors
    fused and takes the early-exit policy from the options."""
    from repro.launch import serve
    argv = ["--arch", "h2o_danube_3_4b", "--reduced"]
    parse = serve.make_parser().parse_args
    args = parse(argv + ["--seed", "3", "--early-exit-tol", "1e-3"])
    cfg, params = serve.load_model(args)
    _, params0 = serve.load_model(parse(argv))
    assert cfg.objective == "diffusion"
    assert not np.array_equal(params["eps_head"], params0["eps_head"])
    eng = serve.build_diffusion_engine(args, cfg, params)
    assert eng.mesh is None
    assert eng.retire is not None and eng.retire.tol == 1e-3
    eng.serve([Request(uid=0, seq_len=8, nfe=2, solver="tab3", seed=0)])
    sigs = [key[0] for key in eng._compiled]
    assert sigs and all(sig[0] == "ab" and sig[2] for sig in sigs)
