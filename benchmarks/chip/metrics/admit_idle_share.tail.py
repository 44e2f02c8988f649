"""Share of the traced window the device sat idle while the host was in admission: ``admit`` and its parts, or a compile (open-loop cells)."""
from chipbench import idle


def read(run):
    return idle.share(run, "admit")
