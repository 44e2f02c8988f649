"""Readers of the MoE expert kernel and of the MoE step programs.

The expert kernel is the Mosaic custom call named ``_moe_experts``
(:mod:`repro.kernels.moe_experts`); the step programs are the engine's
``jit_run`` executors, as :mod:`readers` finds them. Each reader returns
None when the run has no trace (or no step program ran in it); a traced
window in which the kernel never ran reads 0 for the kernel's shares.
"""
from __future__ import annotations

import re

from . import cost, cost_moe, readers, tracing

MOE_EXPERTS = re.compile(
    r"^%_moe_experts\b.*custom_call_target=\"tpu_custom_call\"")


def _kernel_calls(run):
    ops = readers._device_events(run, "ops")
    if not ops:
        return None
    return [e for e in ops if MOE_EXPERTS.search(e.name)]


def _step_s(run) -> float:
    mods = readers._device_events(run, "modules") or []
    tv = run.trace
    return sum(tracing.clipped_ns(e, tv.lo, tv.hi) for e in mods
               if readers.STEP_MODULE.search(e.name)) * 1e-9


def expert_roofline(run):
    """Least time of the expert kernel's calls at the chip's HBM bandwidth
    (bytes from each call's own operand and result shapes, one layer of
    the weight stacks) over their device time, as a share (%)."""
    calls = _kernel_calls(run)
    if calls is None:
        return None
    tv = run.trace
    least = spent = 0.0
    for e in calls:
        c = tracing.clipped_ns(e, tv.lo, tv.hi)
        if c <= 0 or c < e.dur_ns:          # calls wholly in the window
            continue
        b = cost_moe.expert_call_bytes(e.name)
        if b is None:
            continue
        least += b / run.peaks["hbm_bytes_per_s"]
        spent += e.dur_ns * 1e-9
    return 100.0 * least / spent if spent > 0 else 0.0


def expert_share(run):
    """Device time of the expert kernel's calls over the device time of the
    step programs, in the traced window (%)."""
    calls = _kernel_calls(run)
    if calls is None:
        return None
    step = _step_s(run)
    if step <= 0:
        return None
    tv = run.trace
    return 100.0 * sum(tracing.clipped_ns(e, tv.lo, tv.hi)
                       for e in calls) * 1e-9 / step


def assignments_per_position(run):
    """Held-expert assignments per position, layer and NFE over the
    requests finished in the run (``Result.moe_assignments``); None when no
    result carries the count."""
    got = want = 0
    for r in run.records:
        n = getattr(r.result, "moe_assignments", None)
        if n is None or not r.result.nfe:
            continue
        got += n
        want += r.send.seq_len * run.model["n_layers"] * r.result.nfe
    return got / want if want else None


def step_mfu(run):
    """Model FLOPs of the request rows stepped in the traced window over
    the device time of the step programs there, as a share (%) of the
    chip's bf16 peak, as ``step_mfu`` counts them: rows at their true
    length, the held experts at the run's mean assignments per position
    (:func:`assignments_per_position`). A model without experts counts as
    :mod:`cost` does."""
    tv = run.trace
    if tv is None or not run.peaks:
        return None
    step = _step_s(run)
    if step <= 0:
        return None
    if "moe" in run.model:
        a = assignments_per_position(run)
        if a is None:
            return None

        def flops(ln):
            return cost_moe.row_forward_flops(run.model, ln, a)
    else:
        def flops(ln):
            return cost.row_forward_flops(run.model, ln)
    total = sum(flops(ln) for t, lens in run.row_steps
                if tv.t_lo <= t < tv.t_hi for ln in lens)
    if total <= 0:
        return None
    return 100.0 * total / step / run.peaks["bf16_flops_per_s"]
