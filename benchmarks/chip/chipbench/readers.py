"""Arithmetic shared by the metric readers under ``metrics/``.

Every reader takes the run (:class:`cell.Run`) and returns a number, or
None when the run holds nothing to read (no trace, no kernel event, no
request in the window). A share of a peak or of a roofline is never
clipped: a reading over 100% means the operations or bytes are counted
too high, or the time leaves out part of the work.
"""
from __future__ import annotations

import re

from . import cell, cost, tracing

# The step executors are ``jax.jit(run)`` programs of the serving engine
# (``jit_run(<fingerprint>)`` on the trace's module line); the fused AB
# update is the Mosaic custom call named after its jitted wrapper (an
# operation's event name is its HLO text).
STEP_MODULE = re.compile(r"^jit_run\b")
FUSED_AB = re.compile(r"^%_fused_ab_jit\b.*custom_call_target=\"tpu_custom_call\"")


def latency_percentile(run, q: float):
    if not run.open_loop:
        return None
    return cell.percentile(cell.latencies(run), q)


def tokens_per_s(run):
    """Tokens of the work done inside the window, per second of it: each
    finished request's true seq_len, in the share of its solver steps that
    ran inside the window (a step counts when its event reaches the
    client)."""
    if run.open_loop or not run.records:
        return None
    tokens = 0.0
    for r in run.records:
        if r.ok and r.events:
            inside = sum(1 for t in r.events if run.t0 <= t < run.t1)
            tokens += r.send.seq_len * inside / len(r.events)
    return tokens / run.seconds


def queue_wait_p95(run):
    waits = [r.result.queue_wait_s for r in run.records
             if run.in_window(r) and r.ok]
    return cell.percentile(waits, 95)


def rows_per_step(run):
    """Request rows stepped per group step, inside the window."""
    if run.group_steps <= 0:
        return None
    rows = sum(len(lens) for t, lens in run.row_steps
               if run.t0 <= t < run.t1)
    return rows / run.group_steps


def _device_events(run, line: str):
    tv = run.trace
    if tv is None:
        return None
    src = tv.trace.device_ops if line == "ops" else tv.trace.device_modules
    return [e for evs in src.values() for e in evs] or None


def step_mfu(run):
    """Model FLOPs of the request rows stepped in the traced window over
    the device time of the step programs there, as a share (%) of the
    chip's bf16 peak. Rows count at their true length; padding rows, spare
    tiles and bucket tails are not counted."""
    mods = _device_events(run, "modules")
    if not mods:
        return None
    tv = run.trace
    busy = sum(tracing.clipped_ns(e, tv.lo, tv.hi) for e in mods
               if STEP_MODULE.search(e.name)) * 1e-9
    if busy <= 0:
        return None
    flops = sum(cost.row_forward_flops(run.model, ln)
                for t, lens in run.row_steps if tv.t_lo <= t < tv.t_hi
                for ln in lens)
    if flops <= 0:
        return None
    return 100.0 * flops / busy / run.peaks["bf16_flops_per_s"]


def fused_ab_roofline(run):
    """Least time of the fused AB kernel's calls at the chip's HBM
    bandwidth (bytes from each call's own operand and result shapes) over
    their device time, as a share (%)."""
    ops = _device_events(run, "ops")
    if not ops:
        return None
    tv = run.trace
    least = spent = 0.0
    for e in ops:
        if not FUSED_AB.search(e.name):
            continue
        c = tracing.clipped_ns(e, tv.lo, tv.hi)
        if c <= 0 or c < e.dur_ns:          # calls wholly in the window
            continue
        b = cost.fused_ab_bytes(e.name)
        if b is None:
            continue
        least += b / run.peaks["hbm_bytes_per_s"]
        spent += e.dur_ns * 1e-9
    return 100.0 * least / spent if spent > 0 else None


def device_idle_share(run):
    tv = run.trace
    if tv is None or not tv.trace.device_ops:
        return None
    span = tv.hi - tv.lo
    busy = sum(tracing.busy_ns(evs, tv.lo, tv.hi)
               for evs in tv.trace.device_ops.values())
    return 100.0 * (1.0 - busy / (span * len(tv.trace.device_ops)))
