"""peak_bytes_in_use of the fullest chip of the cell, read after the
window, in GB."""


def read(run):
    return run.memory_peak_bytes / 1e9
