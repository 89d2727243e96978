"""1 - union of device-op intervals over the traced interval."""


def read(run):
    if not run.trace or run.rehearsal:
        return None
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
