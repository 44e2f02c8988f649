"""Smoke run of the served diffusion path on a TPU, at full model width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # request-axis data parallel, 4 chips

The default run builds h2o-danube-3-4b at its published widths in bf16
(params random, from ``--seed``) through the serving CLI's own constructors
(``repro.launch.serve``), then serves through ``ServeDriver`` ->
``DiffusionServeEngine`` -> the AOT step executors, at seq_len 256:

* a deterministic AB plan (tab3), a score-normalized one (sndeis2), a
  stochastic AB plan (seeds2: the noise leaf rides in the fused kernel) and
  an RK plan (rho_heun: the unfused path);
* the same tab3 request again in a stacked group of four, which must give
  the solo result bit for bit (the reproducibility invariant);
* the same invariant at seq 32 and 64, whose eps tiles hold 8 and 4 rows:
  the served eps of each row of every group of 1 to 8 rows against the row
  alone, bitwise, and a tab3 request solo against a stacked group of 8;
* one engine with a ``RetirePolicy``, so the kernel's error-pair output
  runs.

It fails unless every request returns tokens in ``[0, vocab)``, the device
is a TPU, and every fused executor holds the compiled kernel
(``tpu_custom_call``). ``--four-chips`` runs only the data-parallel check:
one request set on a 4-device request mesh and on one device, with equal
tokens. ``--reduced`` runs the same phases at the reduced config on any
backend: a rehearsal, whose last line always says ``"ok": false``.

Earlier lines report what ran; the last line is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Without a TPU (and
without ``--reduced``) the script exits non-zero before building anything.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.diffusion import lm as DLM  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.serving.driver import ServeDriver  # noqa: E402
from repro.serving.engine import Request  # noqa: E402

ARCH = "h2o_danube_3_4b"
SEQ_LEN = 256
NFE = 10
SERVED = ("tab3", "sndeis2", "seeds2", "rho_heun")
STACK = 4                 # rows of the stacked tab3 group
SHORT_SEQ_LENS = (32, 64)  # buckets whose eps tiles are wider than 2 rows
GROUP_MAX = 8              # the engine's default max_group
RESULT_TIMEOUT_S = 900.0


class Checks:
    """Named pass/fail records, printed as they are made."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def _serve_args(seed: int, reduced: bool, *extra: str):
    argv = ["--arch", ARCH, "--seed", str(seed), *extra]
    return serve.make_parser().parse_args(argv + (["--reduced"] if reduced
                                                  else []))


def _tokens_ok(check: Checks, name: str, res, vocab: int,
               seq_len: int = SEQ_LEN) -> bool:
    toks = np.asarray(res.tokens)
    ok = (toks.shape == (seq_len,) and np.issubdtype(toks.dtype, np.integer)
          and bool(np.all((toks >= 0) & (toks < vocab))))
    return check(f"tokens[{name}]", ok,
                 f"shape={toks.shape} range=[{toks.min(initial=0)}, "
                 f"{toks.max(initial=0)}] vocab={vocab}")


def _kernels_compiled(check: Checks, label: str, eng) -> None:
    """Every fused executor must hold the Mosaic kernel: the kernel ran
    compiled, not in the interpreter."""
    fused = [(k, c) for k, c in eng._compiled.items() if k[0][2]]
    check(f"fused_executors[{label}]", bool(fused), f"n={len(fused)}")
    for i, ((sig, batch, _s, _m), c) in enumerate(fused):
        check(f"tpu_custom_call[{label}#{i} stochastic={sig[1]} "
              f"err={sig[4]} R={batch}]", "tpu_custom_call" in c.as_text())


def _report(name: str, res) -> None:
    print(f"request {name}: nfe={res.nfe} compile_s={res.compile_s:.3f}"
          f" latency_s={res.latency_s:.4f} early_exit={res.early_exit}"
          f" final_err={res.final_err}", flush=True)


def _drive(check: Checks, eng, requests, vocab: int) -> dict:
    """Serve ``requests`` through a ServeDriver; a request the driver fails
    (its crash handler included) fails the run."""
    out = {}
    with ServeDriver(eng) as drv:
        handles = [(r, drv.submit(r)) for r in requests]
        for req, h in handles:
            name = f"{req.solver} uid={req.uid}"
            try:
                res = h.result(timeout=RESULT_TIMEOUT_S)
            except Exception as e:   # noqa: BLE001 - reported as a failure
                check(f"served[{name}]", False, f"{type(e).__name__}: {e}")
                continue
            _report(name, res)
            if _tokens_ok(check, name, res, vocab):
                out[req.uid] = res
    return out


def _compile_seconds(*engines) -> float:
    return sum(e.metrics.get("serve_compile_seconds_total").value
               for e in engines)


def default_phase(check: Checks, seed: int, reduced: bool) -> None:
    args = _serve_args(seed, reduced)
    t0 = time.perf_counter()
    cfg, params = serve.load_model(args)
    jax.block_until_ready(params)
    print(f"load_model_s {time.perf_counter() - t0:.3f}")
    vocab = cfg.vocab_size
    print(f"model {cfg.name}: n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={vocab} "
          f"dtype={cfg.dtype}", flush=True)
    print(f"param_bytes {sum(x.nbytes for x in jax.tree.leaves(params))}")

    eng = serve.build_diffusion_engine(args, cfg, params)
    check("engine_fused_default", eng._plan("tab3", NFE, None).fused)
    reqs = [Request(uid=i, seq_len=SEQ_LEN, nfe=NFE, solver=s, seed=i)
            for i, s in enumerate(SERVED)]
    solo = _drive(check, eng, reqs, vocab)

    # tab3 is the only member of its plan family above, so it ran as a
    # one-row group; here it is one row of four (engine.serve admits them
    # at one boundary)
    stacked = eng.serve([Request(uid=100 + j, seq_len=SEQ_LEN, nfe=NFE,
                                 solver="tab3", seed=j) for j in range(STACK)])
    by_uid = {r.uid: r for r in stacked}
    for r in stacked:
        _report(f"tab3 stacked uid={r.uid}", r)
        _tokens_ok(check, f"tab3 stacked uid={r.uid}", r, vocab)
    tab3 = eng._plan("tab3", NFE, None).signature
    batches = sorted(k[1] for k in eng._compiled if k[0] == tab3)
    check("solo_vs_stacked_bitwise",
          0 in solo and 100 in by_uid and STACK in batches and 1 in batches
          and np.array_equal(solo[0].tokens, by_uid[100].tokens),
          f"tab3 executor batches={batches}")

    for s_len in SHORT_SEQ_LENS:
        short_tiles(check, eng, cfg, params, s_len, vocab)

    rargs = _serve_args(seed, reduced, "--early-exit-tol", "1e-3")
    eng_r = serve.build_diffusion_engine(rargs, cfg, params)
    _drive(check, eng_r, [Request(uid=200, seq_len=SEQ_LEN, nfe=NFE,
                                  solver="tab3", seed=0)], vocab)
    _kernels_compiled(check, "default", eng)
    _kernels_compiled(check, "retire", eng_r)
    print(f"compile_seconds_total {_compile_seconds(eng, eng_r):.3f}")


def short_tiles(check: Checks, eng, cfg, params, s_len: int,
                vocab: int) -> None:
    """Solo == stacked at a bucket whose eps tile is wider than 2 rows:
    the served eps of every group of 1 to GROUP_MAX rows, row by row
    against each row alone, and a tab3 request solo against the first row
    of a stacked group of GROUP_MAX."""
    tile = DLM.tile_rows(s_len)
    eps = jax.jit(lambda p, x, t, vl: DLM.make_tiled_eps_fn(
        p, cfg, valid_len=vl)(x, t))
    x = jax.random.normal(jax.random.PRNGKey(s_len),
                          (GROUP_MAX, s_len, cfg.d_model), jnp.float32)
    t = jnp.linspace(0.3, 0.9, GROUP_MAX, dtype=jnp.float32)
    lens = jnp.asarray([s_len - 3 * i for i in range(GROUP_MAX)], jnp.int32)
    solo = [np.asarray(eps(params, x[i:i + 1], t[i:i + 1], lens[i:i + 1]))[0]
            for i in range(GROUP_MAX)]
    bad = [(r, i) for r in range(1, GROUP_MAX + 1)
           for i, row in enumerate(np.asarray(eps(params, x[:r], t[:r],
                                                  lens[:r])))
           if not np.array_equal(row, solo[i])]
    check(f"tiled_eps_solo_vs_stacked[seq={s_len} tile={tile}]", not bad,
          f"groups 1..{GROUP_MAX}, mismatched (rows, row)={bad[:8]}")

    def req(uid, seed):
        return Request(uid=uid, seq_len=s_len, nfe=NFE, solver="tab3",
                       seed=seed)
    one = eng.serve([req(300 + s_len, 0)])[0]
    group = {r.uid: r for r in eng.serve(
        [req(400 + s_len + j, j) for j in range(GROUP_MAX)])}
    first = group.get(400 + s_len)
    ok = (first is not None
          and _tokens_ok(check, f"tab3 seq={s_len} solo", one, vocab, s_len)
          and _tokens_ok(check, f"tab3 seq={s_len} stacked", first, vocab,
                         s_len)
          and np.array_equal(one.tokens, first.tokens))
    check(f"solo_vs_stacked_bitwise[seq={s_len} tile={tile}]", ok,
          f"stack={len(group)}")


def four_chips_phase(check: Checks, seed: int, reduced: bool) -> None:
    one = _serve_args(seed, reduced)
    cfg, params = serve.load_model(one)
    vocab = cfg.vocab_size
    solvers = ("tab3",) * 4 + ("seeds2",) * 2 + ("rho_heun",) * 2

    def requests():
        return [Request(uid=i, seq_len=SEQ_LEN, nfe=NFE, solver=s, seed=i)
                for i, s in enumerate(solvers)]

    single = serve.build_diffusion_engine(one, cfg, params)
    want = {r.uid: r for r in single.serve(requests())}
    t_single = _compile_seconds(single)
    # one copy of the weights per device: move them to the host before the
    # mesh engine replicates them, so device 0 never holds two
    host = jax.device_get(params)
    del single, params
    gc.collect()
    meshed = serve.build_diffusion_engine(
        _serve_args(seed, reduced, "--data-parallel"), cfg, host)
    got = {r.uid: r for r in meshed.serve(requests())}
    for uid, res in sorted(got.items()):
        print(f"request {solvers[uid]} uid={uid}: nfe={res.nfe} "
              f"latency_s 1dev={want[uid].latency_s:.4f} "
              f"4dev={res.latency_s:.4f}", flush=True)
        _tokens_ok(check, f"{solvers[uid]} uid={uid} 4dev", res, vocab)
    check("mesh_vs_single_device_tokens",
          sorted(got) == sorted(want) and all(
              np.array_equal(got[u].tokens, want[u].tokens) for u in want),
          f"requests={len(want)} devices={len(jax.devices())}")
    _kernels_compiled(check, "mesh", meshed)
    print(f"compile_seconds_total 1dev={t_single:.3f} "
          f"4dev={_compile_seconds(meshed):.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 4-device data-parallel check")
    ap.add_argument("--reduced", action="store_true",
                    help="rehearse at the reduced config on any backend")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.reduced:
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"compile_cache {serve.enable_compile_cache()}")
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)

    check = Checks()
    check("platform_tpu", dev.platform == "tpu", dev.platform)
    # a rehearsal never stands for the bring-up: its last line says not ok
    check("full_width", not args.reduced, "--reduced" if args.reduced else "")
    try:
        if args.four_chips:
            four_chips_phase(check, args.seed, args.reduced)
        else:
            default_phase(check, args.seed, args.reduced)
    except Exception as e:   # noqa: BLE001 - any phase error fails the run
        traceback.print_exc()
        check("phase_completed", False, f"{type(e).__name__}: {e}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    ok = not check.failed
    if not ok:
        print(f"failed checks: {check.failed}")
    print(json.dumps({"ok": ok, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
