"""Share of its roofline that the fused decode step reaches: per launch,
the larger of its operations over the bf16 peak and its HBM bytes (the
shard's weights, the live rows' cached keys and values) over the peak
bandwidth, against the launch's device time in the trace."""

from chipbench.record import calls_in_window
from chipbench.trace import module_time

PROGRAM = "jit_fused"


def read(run):
    calls = calls_in_window(run.calls.get("fused", []), run.window)
    if run.trace is None or not run.trace.modules or not calls:
        return None
    total, n = module_time(run.trace.modules[0], PROGRAM, run.trace.window())
    if not n or total <= 0:
        return None
    pk = run.peaks
    floor = sum(max(c.info["flops"] / pk["bf16_flops"],
                    c.info["bytes"] / pk["hbm_bytes_per_s"]) for c in calls)
    return 100.0 * (floor / len(calls)) / (total / n)
