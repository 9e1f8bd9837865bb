"""Mean device time of one fused decode step: the ``jit_fused`` program's
launches in the trace of the window."""

from chipbench.trace import module_time

PROGRAM = "jit_fused"


def read(run):
    if run.trace is None or not run.trace.modules:
        return None
    total, n = module_time(run.trace.modules[0], PROGRAM, run.trace.window())
    return 1e3 * total / n if n else None
