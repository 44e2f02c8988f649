"""Share of the traced window with no operation on the device (closed-loop cells)."""
from chipbench import readers


def read(run):
    return readers.device_idle_share(run)
