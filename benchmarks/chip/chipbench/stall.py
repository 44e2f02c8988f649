"""Stalls of the benchmark's own process, seen from inside it.

A ticker thread wakes every ``TICK_S``; a wake-up more than ``STALL_S``
late is a stall. For each stall it keeps its length and what the process
did meanwhile: CPU seconds (about the stall's length when some thread ran
without letting the ticker in, about none when the process did not run
or waited in a call that held the interpreter lock), major page faults
and involuntary context switches.
"""
from __future__ import annotations

import dataclasses
import resource
import threading
import time

TICK_S = 0.01
STALL_S = 0.25


@dataclasses.dataclass
class Stall:
    at: float          # perf_counter when the ticker last ran before it
    wall_s: float
    cpu_s: float
    majflt: int
    nivcsw: int


def _usage():
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime, ru.ru_majflt, ru.ru_nivcsw


class StallWatch:
    """Records stalls between ``start()`` and ``stop()``."""

    def __init__(self):
        self.stalls: list[Stall] = []
        self._stop = threading.Event()
        self._th = threading.Thread(target=self._run, daemon=True)

    def start(self) -> "StallWatch":
        self._th.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._th.join()

    def _run(self) -> None:
        last, (cpu, flt, csw) = time.perf_counter(), _usage()
        while not self._stop.is_set():
            time.sleep(TICK_S)
            now, (cpu2, flt2, csw2) = time.perf_counter(), _usage()
            if now - last - TICK_S > STALL_S:
                self.stalls.append(Stall(last, now - last, cpu2 - cpu,
                                         flt2 - flt, csw2 - csw))
            last, cpu, flt, csw = now, cpu2, flt2, csw2

    def within(self, t0: float, t1: float) -> list[Stall]:
        return [s for s in self.stalls if t0 <= s.at < t1]

    @staticmethod
    def summary(stalls: list[Stall]) -> str:
        wall = sum(s.wall_s for s in stalls)
        cpu = sum(s.cpu_s for s in stalls)
        return (f"{len(stalls)} stalls over {STALL_S} s, longest "
                f"{max((s.wall_s for s in stalls), default=0.0):.3f} s, "
                f"{wall:.3f} s in all with {cpu:.3f} s of process cpu, "
                f"{sum(s.majflt for s in stalls)} major faults, "
                f"{sum(s.nivcsw for s in stalls)} preempted")
