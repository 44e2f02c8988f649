"""MoE expert kernel: least time at HBM bandwidth over device time (open-loop cells)."""
from chipbench import readers_moe


def read(run):
    return readers_moe.expert_roofline(run)
