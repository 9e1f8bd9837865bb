"""Share of the window's wall time outside the engines' ``step`` and
``_prefill`` calls: the client driver, the RPC plane and the simulator."""

from chipbench.record import wall_inside


def read(run):
    calls = run.calls.get("step", []) + run.calls.get("prefill", [])
    if not calls:
        return None
    return 100.0 * (1.0 - wall_inside(calls, run.window) / run.window_s)
