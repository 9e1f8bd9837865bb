"""Mean host time a request submitted in the window waits for admission
before its first token: in the client's queue (``client.queue``, also after
a retry) and for a slot in each shard's engine (``engine.admit_wait``)."""

from chipbench.phases import first_token_phases, seconds


def read(run):
    reqs = first_token_phases(run).values()
    if not reqs:
        return None
    wait = sum(seconds(r) for ph in reqs for r in ph
               if r.name in ("client.queue", "engine.admit_wait"))
    return 1e3 * wait / len(reqs)
