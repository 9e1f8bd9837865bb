"""Mean host wall time of one ``BatchEngine._prefill`` call on a shard:
the dense prefill and the copy of its keys and values into pool pages."""

from chipbench.record import calls_in_window


def read(run):
    calls = calls_in_window(run.calls.get("prefill", []), run.window)
    if not calls:
        return None
    return 1e3 * sum(c.end - c.start for c in calls) / len(calls)
