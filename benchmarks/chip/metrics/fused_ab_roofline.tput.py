"""Fused AB kernel: least time at HBM bandwidth over device time (closed-loop cells)."""
from chipbench import readers


def read(run):
    return readers.fused_ab_roofline(run)
