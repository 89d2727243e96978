"""Executables built inside the window: the stage compiler's counter
plus XLA backend-compile events.  0 expected."""


def read(run):
    c = run.window_compiles
    return c["jit_cache"] + c["backend"]
