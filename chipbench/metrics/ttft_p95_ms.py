"""95th percentile, over every request submitted in the window, of the
wait from submit to its first token."""

from chipbench.record import first_token_waits, percentile


def read(run):
    p = percentile(first_token_waits(run), 95)
    return None if p is None else 1e3 * p
