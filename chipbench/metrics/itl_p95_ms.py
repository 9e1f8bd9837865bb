"""95th percentile of every gap between consecutive tokens of a request,
the later token stamped in the window; stalls behind other sessions'
prefills included."""

from chipbench.record import percentile, token_gaps


def read(run):
    p = percentile(token_gaps(run), 95)
    return None if p is None else 1e3 * p
