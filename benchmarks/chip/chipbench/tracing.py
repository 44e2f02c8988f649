"""Reduction of a profiler trace to device busy time, per-program and
per-kernel device time, and idle gaps attributed to host spans.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
A device is a plane named ``/device:<kind>:<n>``; its ``XLA Ops`` line holds
one event per operation that ran, its ``XLA Modules`` line one event per
program run. Host spans are the events of the host plane's lines, among
them the engine's ``Tracer(annotate=True)`` spans and the benchmark's own
``bench.window`` span, which bounds the traced window.
"""
from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
NAME_CHARS = 120        # of an operation's HLO text, in the breakdown


@dataclasses.dataclass
class Event:
    name: str               # a device operation's name is its HLO text
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """What the reduction needs of one trace, as plain lists."""
    device_ops: dict        # device plane name -> [Event] (XLA Ops)
    device_modules: dict    # device plane name -> [Event] (XLA Modules)
    host_spans: list        # [Event] from every host line


def _events(line) -> list[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def read(path: str) -> Trace:
    """Read the ``.xplane.pb`` at ``path`` (or the newest one under it)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    ops, mods, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    mods[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return Trace(ops, mods, host)


def window(tr: Trace) -> tuple[float, float]:
    """(start, end) of the benchmark's ``bench.window`` span, else the
    extent of every device operation."""
    spans = [e for e in tr.host_spans if e.name == WINDOW_SPAN]
    if spans:
        w = max(spans, key=lambda e: e.dur_ns)
        return w.start_ns, w.end_ns
    evs = [e for evs in tr.device_ops.values() for e in evs]
    if not evs:
        raise ValueError("trace holds neither a window span nor device ops")
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    (start, end) pairs."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merge(((e.start_ns, e.end_ns)
                                        for e in events), lo, hi))


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of the device in [lo, hi]: where no operation ran."""
    out, t = [], lo
    for a, b in merge(((e.start_ns, e.end_ns) for e in events), lo, hi):
        if a > t:
            out.append((t, a))
        t = b
    if hi > t:
        out.append((t, hi))
    return out


def clipped_ns(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))


def is_container(e: Event) -> bool:
    """A control-flow operation, whose event spans its body's events (the
    op's name is its HLO text, ``%while.3 = ...``)."""
    head = e.name.lstrip("%").split("=", 1)[0].strip()
    return head.split(".", 1)[0] in ("while", "conditional", "call")


def attribute(gap: tuple[float, float], spans: list[Event],
              names) -> str:
    """The host span, among ``names`` (a span nested in one, ``a.b``,
    counts as ``a.b``), that covers most of ``gap`` (the innermost on a
    tie), or ``unattributed``."""
    a, b = gap
    best, best_cover, best_len = "unattributed", 0.0, float("inf")
    for s in spans:
        if s.name.split(".", 1)[0] not in names:
            continue
        cover = min(b, s.end_ns) - max(a, s.start_ns)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover
                                  and s.dur_ns < best_len):
            best, best_cover, best_len = s.name, cover, s.dur_ns
    return best


def top_ops(events, lo: float, hi: float, n: int = 10):
    """The n operation names (the head of their HLO text) that took most
    device time in [lo, hi]."""
    tot: dict[str, float] = {}
    for e in events:
        c = clipped_ns(e, lo, hi)
        if c > 0:
            key = e.name[:NAME_CHARS]
            tot[key] = tot.get(key, 0.0) + c
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
