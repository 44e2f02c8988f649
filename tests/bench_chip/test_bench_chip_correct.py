"""The comparison that decides ``correct``, on the CPU at a tiny size: the
served path passes it, the float8 control and every fault the served
cells can have fail it."""
import io
import json
import pathlib
import re
import sys
from contextlib import redirect_stderr, redirect_stdout

import jax
import jax.numpy as jnp
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from chipbench import cell as C  # noqa: E402
from chipbench import spec, traffic  # noqa: E402

TINY = {"model": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 256}}
CELL = "danube3-4b.chat-s256"
# bursts of 16 (the burst-s32 mix) fill groups of several rows; few
# lengths in one bucket keep the warm-up small on the CPU
BURSTS = json.loads((BENCH / "traffic" / "burst-s32.json").read_text())
SMALL = dict(BURSTS, seq_len={"dist": "uniform", "min": 48, "max": 64},
             buckets=[64], check_requests=8, lead_s=0.5)
# at this size six seeds read at most 5.7e-4 (program) and at least
# 4.6e-3 (control); the cells' own limits come from the chip at their sizes
TINY_LIMIT = 2e-3


@pytest.fixture
def no_x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", prev)


@pytest.fixture
def small_cell(monkeypatch):
    load = spec.load

    def small(name, *a, **k):
        c = load(name, *a, **k)
        c.traffic = SMALL
        c.limits = dict(c.limits, max_logit_gap=TINY_LIMIT)
        return c
    monkeypatch.setattr(spec, "load", small)
    return small


def _run(seed=5):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1.5", "--trace", "0"], rehearsal=TINY)
    assert rc == 0, err.getvalue()[-2000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["compared"]["max_logit_gap"]["limit"] == TINY_LIMIT
    return line


def test_the_served_path_is_correct(no_x64, small_cell):
    line = _run()
    assert line["correct"] is True
    assert line["compared"]["max_logit_gap"]["value"] <= TINY_LIMIT


def test_a_token_altered_where_it_is_produced_fails(no_x64, small_cell,
                                                    monkeypatch):
    from repro.diffusion import lm
    decode = lm.decode_tokens

    def altered(params, cfg, x0):
        toks = decode(params, cfg, x0)
        return toks.at[..., 0].set((toks[..., 0] + 1) % cfg.vocab_size)
    monkeypatch.setattr(lm, "decode_tokens", altered)
    assert _run()["correct"] is False


def test_a_step_that_returns_its_state_unchanged_fails(no_x64, small_cell,
                                                       monkeypatch):
    from repro.core import sampler
    monkeypatch.setattr(sampler, "step", lambda plan, k, state, *a, **kw:
                        state)
    assert _run()["correct"] is False


def test_half_of_each_group_left_out_fails(no_x64, small_cell, monkeypatch):
    """The eps-net runs for the first half of a group's rows only."""
    from repro.diffusion import lm
    tiled = lm.make_tiled_eps_fn

    def half(params, cfg, **kw):
        fn = tiled(params, cfg, **kw)

        def eps(x, t):
            e = fn(x, t)
            keep = jnp.arange(x.shape[0]) < (x.shape[0] + 1) // 2
            return jnp.where(keep[:, None, None], e, 0.0)
        return eps
    monkeypatch.setattr(lm, "make_tiled_eps_fn", half)
    assert _run()["correct"] is False


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_float8_control_reads_above_the_program(no_x64, small_cell,
                                                    seed):
    """The reference in the program's place with every eps-net matmul
    operand in float8 e4m3 (one precision below the configuration's
    bfloat16), through the command's own comparison and limit: the run
    comes out not correct, and its gap is wider than the served path's."""
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1.5", "--trace", "0", "--control", "1"],
                      rehearsal=TINY)
    assert rc == 0, err.getvalue()[-2000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is False
    cmp = line["compared"]["max_logit_gap"]
    assert cmp["limit"] == TINY_LIMIT < cmp["value"]
    prog = re.search(r"program's own max_logit_gap (\S+)", err.getvalue())
    assert prog and float(prog.group(1)) <= TINY_LIMIT


def test_every_step_of_every_request_row_is_noted(no_x64):
    """The per-step row notes that ``step_mfu`` and ``rows_per_step`` read:
    each request steps once per NFE of its tab3 solve, at its true length."""
    from collections import Counter
    cell = spec.load(CELL)
    cell.traffic = SMALL
    lead = SMALL["lead_s"]
    su = C.prepare(cell, seed=3, horizon=lead + 1.5 + C.TAIL_S, trace=False,
                   t_start=0.0, root=BENCH, rehearsal=TINY)
    sends = traffic.schedule(SMALL, su.t_seed, lead + 1.5 + C.TAIL_S)
    ld = C.serve_load(su, sends, lead=lead, seconds=1.5)
    done = [r for r in ld.records if r.ok]
    assert done and all(r.ok for r in ld.records if ld.t0 <= r.t_due < ld.t1)
    noted = Counter(ln for _t, lens in ld.row_steps for ln in lens)
    want = Counter()
    for r in done:
        want[r.send.seq_len] += r.result.nfe
    assert all(noted[ln] >= n for ln, n in want.items())
    # requests cut off at the drain's end stepped part of their solve
    assert sum(want.values()) <= sum(noted.values()) \
        <= sum(r.send.nfe for r in ld.records)
