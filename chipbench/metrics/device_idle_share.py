"""Share of the traced window in which no operation ran on the device
(1 - the union of op intervals over the window), mean over the chips."""

from chipbench.trace import busy_s


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    win = t.window()
    used = t.ops[:run.chips]
    busy = sum(busy_s(o, win) for o in used) / len(used)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (win[1] - win[0]))
