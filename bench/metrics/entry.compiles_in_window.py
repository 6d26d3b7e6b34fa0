"""Programs JAX compiled, or loaded from its cache, between the window's
start and its end.  Expected 0: a run that reads more timed the compiler."""


def read(run):
    return run.compiles_in_window
