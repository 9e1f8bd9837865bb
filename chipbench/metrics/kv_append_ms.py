"""Mean device time of one KV pool append: the ``jit__append_rows``
program's launches (one scatter of a step's new rows into the resident
pool) in the trace of the window."""

from chipbench.trace import module_time

PROGRAM = "jit__append_rows"


def read(run):
    if run.trace is None or not run.trace.modules:
        return None
    total, n = module_time(run.trace.modules[0], PROGRAM, run.trace.window())
    return 1e3 * total / n if n else None
