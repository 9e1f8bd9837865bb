"""Live rows per engine step in the window, over every shard: the
``rows`` of each ``engine.step`` span, which sum to the step's increment of
``BatchEngine.stats["step_sessions"]`` as the spans count its ``steps``."""

from chipbench.phases import in_window


def read(run):
    steps = in_window(run, "engine.step")
    if not steps:
        return None
    return sum(r.attrs["rows"] for r in steps) / len(steps)
