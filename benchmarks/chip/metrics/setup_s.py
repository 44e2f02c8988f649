"""Process start to the opening of the window."""


def read(run):
    return run.setup_s
