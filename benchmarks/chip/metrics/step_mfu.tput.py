"""Model FLOPs over step-program device time, as a share of the bf16 peak (closed-loop cells)."""
from chipbench import readers


def read(run):
    return readers.step_mfu(run)
