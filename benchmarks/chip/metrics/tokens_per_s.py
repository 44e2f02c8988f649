"""Tokens of the work done inside the window, per second (closed loop):
each request's true seq_len in the share of its steps that ran inside."""
from chipbench import readers


def read(run):
    return readers.tokens_per_s(run)
