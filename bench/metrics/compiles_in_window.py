"""XLA compiles the program made inside the window (the persistent
cache is off there, so none is a cache hit)."""


def read(run):
    return run.compiles_in_window
