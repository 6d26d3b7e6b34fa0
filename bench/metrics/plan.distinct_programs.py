"""Names of the jitted programs that ran on the device in the traced
window.  A count of names, not of programs: the trace's reduction keeps a
program's name and not its id or shapes, so four ``join_gather`` of four
shapes count once, and a cold checkout compiles more programs than this
reads (``setup_s``, which it moves, has them all).  It moves when a stage
becomes a program of its own or stops being one."""


def read(run):
    t = run.trace
    if not t:
        return None
    return len(t["modules_s"])
