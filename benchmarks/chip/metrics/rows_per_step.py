"""Request rows stepped per engine group step inside the window."""
from chipbench import readers


def read(run):
    return readers.rows_per_step(run)
