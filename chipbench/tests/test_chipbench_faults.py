"""The check refuses the bfloat16 control put in the program's place, and
each fault planted in the served path: KV state never written, a token
altered where it is produced, the activations between the pipeline
shards left out, half of a batch's rows left out (given the others'
results)."""

import time
import numpy as np
import pytest

import chipbench_tiny
from chipbench import run as bench_run
from repro.serving.batch import BatchEngine


def _run(seed=11):
    return bench_run.run(chipbench_tiny.cell(), chipbench_tiny.bench(),
                         seed=seed, seconds=0.4, traced=False,
                         peaks=chipbench_tiny.PEAKS,
                         t_start=time.perf_counter())


def test_the_bfloat16_control_is_not_correct():
    """The reference in bfloat16 put in the program's place: at each
    position of the served prompts and tokens, its logits and the token it
    puts first, judged by the same limits, come out as not correct, while
    the program's readings of the same run stay within them."""
    line = bench_run.run(chipbench_tiny.cell(), chipbench_tiny.bench(),
                         seed=12, seconds=1.0, traced=False,
                         peaks=chipbench_tiny.PEAKS,
                         t_start=time.perf_counter(), control=True)
    d, limits = line["check_detail"], chipbench_tiny.cell()["limits"]
    assert line["correct"] is False
    assert all(d["program_" + k] <= v["limit"] for k, v in limits.items())
    assert any(line["checks"][k]["value"] > v["limit"]
               for k, v in limits.items())
    # the logits' deviation separates whatever requests the window
    # finished; a token flip may or may not fall into a small sample
    assert d["worst_logit_rms_sd"] > limits["worst_logit_rms_sd"]["limit"]


def _state_unchanged(monkeypatch):
    def append(self, st, kn, vn):          # the step's KV never stored
        st.length += 1
    monkeypatch.setattr(BatchEngine, "_pool_append", append)


def _token_altered(monkeypatch):
    real = BatchEngine._step_fused

    def step(self, sessions, x):
        out, served, cost = real(self, sessions, x)
        if self.module.is_last and len(out):
            out = np.array(out)
            out[:, 3] = out.max(axis=-1) + 1.0
        return out, served, cost
    monkeypatch.setattr(BatchEngine, "_step_fused", step)


def _hop_left_out(monkeypatch):
    real = BatchEngine.step

    def step(self, sessions, x, evict=None):
        if not self.module.is_first:
            x = np.zeros_like(np.asarray(x))
        return real(self, sessions, x, evict=evict)
    monkeypatch.setattr(BatchEngine, "step", step)


def _half_batch_left_out(monkeypatch):
    real = BatchEngine._step_fused

    def step(self, sessions, x):
        out, served, cost = real(self, sessions, x)
        half = len(out) // 2
        if self.module.is_last and half:
            out = np.array(out)                # the rest copy the first rows
            out[half:2 * half] = out[:half]
        return out, served, cost
    monkeypatch.setattr(BatchEngine, "_step_fused", step)


@pytest.mark.parametrize("plant", [_state_unchanged, _token_altered,
                                   _hop_left_out, _half_batch_left_out])
def test_a_planted_fault_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    line = _run()
    assert line["correct"] is False
    assert line["checks"]["worst_gap_sd"]["value"] > 0.5
