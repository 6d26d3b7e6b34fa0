"""Process start to window start: imports, native library, data from the
seed, tables to the chips, warm-up of the cell's own shapes."""


def read(run):
    return run.setup_s
