"""Process start to the start of the window, on the host's clock."""


def read(run):
    return run.setup_s
