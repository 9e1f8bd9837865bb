"""Tail and rate arithmetic of the end-to-end readers on synthetic stamps."""

import numpy as np
import pytest

from chipbench_tiny import CONFIG, MIX, PEAKS
from chipbench import record, spec
from chipbench.timing import Call


def _run(requests, window=(10.0, 20.0)):
    return record.Run(config=CONFIG, traffic=MIX, chips=1, peaks=PEAKS,
                      window=window, setup_s=3.5, requests=requests)


def _req(submitted, stamps, n=None, s=8):
    r = record.Request(np.zeros(s, np.int32), n or len(stamps), submitted)
    r.stamps = list(stamps)
    r.tokens = [1] * len(stamps)
    r.finished = len(stamps) == r.n_tokens
    return r


def _read(name, run):
    return spec.reader(name)(run)


def test_rate_counts_only_tokens_stamped_in_window():
    run = _run([_req(9.0, [9.5, 10.5, 11.0]), _req(19.0, [19.5, 20.5])])
    assert _read("tokens_per_s", run) == pytest.approx(3 / 10.0)


def test_itl_tail_sees_a_stall_inside_the_window():
    # 19 gaps of 0.1 s and one stall of 2.0 s in the window
    stamps = [10.0 + 0.1 * i for i in range(20)]
    stall = stamps + [stamps[-1] + 2.0]
    run = _run([_req(9.9, stall)])
    gaps = sorted(record.token_gaps(run))
    assert len(gaps) == 20 and gaps[-1] == pytest.approx(2.0)
    want = 1e3 * float(np.percentile(gaps, 95))
    assert _read("itl_p95_ms", run) == pytest.approx(want)
    assert _read("itl_p95_ms", run) > 100.0 + 1e-6   # the stall lifts the tail


def test_itl_counts_a_gap_by_its_later_token():
    run = _run([_req(9.0, [9.8, 10.3, 20.4])])
    assert record.token_gaps(run) == [pytest.approx(0.5)]


def test_ttft_over_requests_submitted_in_window():
    reqs = [_req(9.0, [9.2])] + [_req(11.0 + i, [11.0 + i + 0.01 * (i + 1)])
                                 for i in range(8)]
    run = _run(reqs)
    waits = sorted(record.first_token_waits(run))
    assert waits == pytest.approx([0.01 * (i + 1) for i in range(8)])
    assert _read("ttft_p95_ms", run) == pytest.approx(
        1e3 * float(np.percentile(waits, 95)))


def test_empty_window_reads_nothing():
    run = _run([])
    assert _read("itl_p95_ms", run) is None
    assert _read("ttft_p95_ms", run) is None
    assert _read("mfu", run) is None
    assert _read("setup_s", run) == 3.5


def test_host_share_and_call_means():
    run = _run([])
    run.calls = {"step": [Call(10.0, 12.0, {}), Call(19.0, 21.0, {})],
                 "prefill": [Call(13.0, 14.0, {"tokens": 8})],
                 "fused": [Call(10.0, 10.5, {"input_wait": 0.2}),
                           Call(30.0, 30.5, {"input_wait": 9.0})]}
    # 2 + 1 (clipped at 20) + 1 s of 10 inside engine calls
    assert _read("host_other_share", run) == pytest.approx(60.0)
    assert _read("prefill_ms", run) == pytest.approx(1000.0)
    assert _read("kv_copy_ms", run) == pytest.approx(200.0)
