"""Fused AB kernel: least time at HBM bandwidth over device time (open-loop cells)."""
from chipbench import readers


def read(run):
    return readers.fused_ab_roofline(run)
