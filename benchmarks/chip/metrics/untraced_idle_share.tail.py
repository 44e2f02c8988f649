"""Share of the traced window the device sat idle with no program span open on the host: what the tracing does not cover (open-loop cells)."""
from chipbench import idle


def read(run):
    return idle.share(run, "untraced")
