"""Share of the traced window the device sat idle while the host handed work on: decodes, the step-event fan-out, the driver's inbox and its hand-back of results (open-loop cells)."""
from chipbench import idle


def read(run):
    return idle.share(run, "handoff")
