"""The trace reduction on small synthetic event lists, and ``extract`` on
a trace recorded here (host spans only: the CPU has no device plane)."""

import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on the path)
from chipbench import trace as tr

WIN = (0.0, 10.0)
OPS = [("fusion.1", 1.0, 2.0), ("dot.2", 1.5, 3.0),     # overlap: 1..3
       ("fusion.1", 5.0, 6.0), ("copy.3", 9.5, 11.0)]   # clipped at 10
HOST = [("window", 0.0, 10.0), ("client", 0.0, 10.0),
        ("fused_step", 3.0, 7.0), ("kv_copy", 3.0, 4.5),
        ("prefill", 8.0, 9.0)]


def test_busy_is_the_union_clipped_to_the_window():
    assert tr.busy_s(OPS, WIN) == pytest.approx(2.0 + 1.0 + 0.5)


def test_module_time_counts_launches_starting_in_window():
    mods = [("jit_fused", 1.0, 1.5), ("jit_fused(1)", 4.0, 4.25),
            ("jit_fused_other", 5.0, 6.0), ("jit_fused", 12.0, 13.0),
            ("jit__lambda_", 2.0, 2.5)]
    total, n = tr.module_time(mods, "jit_fused", WIN)
    assert n == 2 and total == pytest.approx(0.75)


def test_top_ops_by_total_time_of_each_kind():
    ops = OPS + [("%fusion.7 = f32[8,128]{1,0} fusion(f32[8,128] %p), "
                  "kind=kLoop", 6.5, 6.75)]
    assert tr.top_ops(ops, WIN, k=2) == [["fusion", pytest.approx(2.25)],
                                         ["dot", pytest.approx(1.5)]]


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(tr.idle_gaps(OPS, HOST, WIN))
    # idle: 0..1 client, 3..5 -> mid 4.0 in kv_copy, 6..9.5 -> mid 7.75 client
    assert gaps == {"client": pytest.approx(1.0 + 3.5),
                    "kv_copy": pytest.approx(2.0)}
    assert sum(gaps.values()) == pytest.approx(10.0 - tr.busy_s(OPS, WIN))


def test_idle_gaps_without_spans_are_none():
    assert tr.idle_gaps([], [], (0.0, 2.0)) == [["none", pytest.approx(2.0)]]


def test_extract_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("fused_step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.extract(str(tmp_path))
    names = [n for n, _, _ in t.host]
    assert names.count("fused_step") == 2 and names.count("window") == 1
    lo, hi = t.window()
    assert all(lo <= s <= e <= hi for n, s, e in t.host if n == "fused_step")
    assert t.ops == [] and t.modules == []
    assert "window" in tr.summary(t)
