"""MoE expert kernel's device time over the step programs' (open-loop cells)."""
from chipbench import readers_moe


def read(run):
    return readers_moe.expert_share(run)
