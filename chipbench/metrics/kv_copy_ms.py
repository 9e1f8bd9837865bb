"""Mean wait of a fused decode step for its arguments: the step's small
inputs and the previous step's append into the resident KV page pool."""

from chipbench.record import calls_in_window


def read(run):
    calls = calls_in_window(run.calls.get("fused", []), run.window)
    if not calls:
        return None
    return 1e3 * sum(c.info["input_wait"] for c in calls) / len(calls)
