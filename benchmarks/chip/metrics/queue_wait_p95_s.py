"""95th percentile of the engine's submit-to-admission wait (Result.queue_wait_s) of the window's requests."""
from chipbench import readers


def read(run):
    return readers.queue_wait_p95(run)
