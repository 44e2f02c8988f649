"""Device idle time split by the program span the host was in.

Each device's idle intervals inside the traced window are sorted, instant
by instant, by the innermost program span open then: the latest-started
host event whose name's first dotted component is one of the serving
program's span names below (``admit.form.prior`` counts as ``admit``).
Other host events (the runtime's own, the benchmark's window) are not
program spans. Shares are % of window x devices, summed as
``readers.device_idle_share`` sums, so the five classes add up to it.

The span names are written out here, as ``cell.HOST_SPANS`` are, so a
rename in the program cannot move the yardstick: a renamed span falls to
``untraced``.
"""
from __future__ import annotations

import heapq

from . import tracing

CLASSES = {
    "admit": ("admit", "compile"),
    "step": ("dispatch", "step_wait"),
    "handoff": ("decode", "fanout", "inbox", "resolve"),
    "wait": ("idle",),
}
UNTRACED = "untraced"
_CLASS_OF = {name: cls for cls, names in CLASSES.items() for name in names}


def class_of(path: str) -> str | None:
    """The class of a program span's dotted path, None for another event."""
    return _CLASS_OF.get(path.split(".", 1)[0])


def segments(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """[lo, hi] cut into (start, end, class) stretches: the class of the
    innermost program span open over each, ``untraced`` where none is."""
    evs = sorted((e.start_ns, e.end_ns, cls) for e in spans
                 if (cls := class_of(e.name)) is not None
                 and e.end_ns > lo and e.start_ns < hi)
    cuts = sorted({lo, hi} | {t for a, b, _ in evs for t in (a, b)
                              if lo < t < hi})
    out: list[list] = []
    open_: list = []            # (-start, end, class): latest start on top
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(evs) and evs[i][0] <= a:
            s, e, cls = evs[i]
            heapq.heappush(open_, (-s, e, cls))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        cls = open_[0][2] if open_ else UNTRACED
        if out and out[-1][2] == cls:
            out[-1][1] = b
        else:
            out.append([a, b, cls])
    return [(a, b, c) for a, b, c in out]


def shares(run) -> dict | None:
    """% of the traced window x devices that each class held the device
    idle; None without a trace or a device operation."""
    tv = run.trace
    if tv is None or not tv.trace.device_ops:
        return None
    segs = segments(tv.trace.host_spans, tv.lo, tv.hi)
    tot = dict.fromkeys([*CLASSES, UNTRACED], 0.0)
    for evs in tv.trace.device_ops.values():
        j = 0
        for a, b in tracing.gaps(evs, tv.lo, tv.hi):
            while segs[j][1] <= a:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < b:
                s, e, cls = segs[k]
                tot[cls] += min(b, e) - max(a, s)
                k += 1
    denom = (tv.hi - tv.lo) * len(tv.trace.device_ops)
    return {cls: 100.0 * v / denom for cls, v in tot.items()}


def share(run, cls: str) -> float | None:
    got = shares(run)
    return None if got is None else got[cls]
