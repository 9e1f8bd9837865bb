"""``kv_append_ms``: the mean device time of the pool's append program,
read from the launches of ``jit__append_rows`` that start in the window,
and silent where the run has no device trace or no launch."""

import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on the path)
from chipbench import record, spec
from chipbench.trace import Reduced

WIN = (0.0, 10.0)


def run_with(trace):
    r = record.Run(config={}, traffic={}, chips=1, peaks={}, window=WIN,
                   setup_s=0.0, requests=[])
    r.trace = trace
    return r


def test_kv_append_ms_is_the_mean_launch_in_the_window():
    mods = [("jit__append_rows", 1.0, 1.002),
            ("jit__append_rows(3)", 4.0, 4.004),
            ("jit__append_rows", 11.0, 12.0),          # after the window
            ("jit__write_prefill", 2.0, 3.0), ("jit_fused", 5.0, 5.02)]
    trace = Reduced(ops=[[]], modules=[mods], host=[("window",) + WIN])
    assert spec.reader("kv_append_ms")(run_with(trace)) == pytest.approx(3.0)


@pytest.mark.parametrize("trace", [None, Reduced([], [], []),
                                   Reduced([[]], [[("jit_fused", 1.0, 2.0)]],
                                           [("window",) + WIN])],
                         ids=["untraced", "no-device", "no-append"])
def test_kv_append_ms_is_silent_without_launches(trace):
    assert spec.reader("kv_append_ms")(run_with(trace)) is None
