"""Share of the first-token wait that requests spend held on the
simulated CPU charge (``rpc.cpu_charge``: ``yield ctx.cpu(cost)`` after the
real prefill, the cost being ``BatchEngine._cost``'s virtual roofline),
host time summed over every request submitted in the window, over the sum
of their first-token waits."""

from chipbench.phases import first_token_phases, seconds


def read(run):
    reqs = first_token_phases(run).values()
    if not reqs:
        return None
    ttft = sum((ph[-1].t1_ns - ph[0].t0_ns) * 1e-9 for ph in reqs)
    charge = sum(seconds(r) for ph in reqs for r in ph
                 if r.name == "rpc.cpu_charge")
    return 100.0 * charge / ttft
