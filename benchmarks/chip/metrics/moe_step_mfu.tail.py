"""Model FLOPs of an MoE eps-net over step-program device time, as a share of the bf16 peak (open-loop cells)."""
from chipbench import readers_moe


def read(run):
    return readers_moe.step_mfu(run)
