"""Fused paged-decode vs per-slot decode.

Drives two :class:`~repro.serving.batch.BatchEngine` configurations
over the same prompt/token feed at example scale:

* ``unfused`` — the per-slot fallback loop (one ``module.apply`` per
  session per token: M weight passes per decode step),
* ``fused``   — the batched paged-attention path (one weight pass per
  step, KV gathered from the shared page pool).

Throughput is tokens per *simulated* second under the engine's roofline
cost model (max of compute time and weight+KV bandwidth time at
``PEER_FLOPS``/``PEER_BW``): decode at batch M is bandwidth-bound, so
charging the weight read once per batch instead of once per session is
the fused win and the simnet cost model prices exactly that.  Cache
bytes are the engine's actual resident pool/cache bytes.

``--kernel-smoke`` gates: fused ≥2× unfused tokens/s.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.core.simnet import Sim
from repro.models import ops_for
from repro.serving.batch import BatchEngine
from repro.serving.sharded import ShardModule

N_SESSIONS = 8
PROMPT_LEN = 12
DECODE_STEPS = 48


def _build_engine(cfg, params, sim: Sim, **kw) -> BatchEngine:
    module = ShardModule(cfg, params, (0, cfg.n_layers),
                        is_first=True, is_last=True)
    return BatchEngine(module, sim, n_slots=N_SESSIONS, page_size=8, **kw)


def _drive(eng: BatchEngine, sim: Sim, feed: List[np.ndarray] = None,
           ) -> Tuple[float, float, float, np.ndarray, List[np.ndarray]]:
    """Open N sessions and decode.  Without ``feed``, tokens are the
    engine's own greedy argmax; with ``feed`` (a recorded run's per-step
    token batches), the exact same tokens are replayed so two engines'
    logits differ only by their cache numerics.  Returns
    (decode_cost_s, tokens, cache_bytes, last_logits, fed_tokens)."""
    rng = np.random.default_rng(11)
    sessions = [f"s{i}" for i in range(N_SESSIONS)]
    prompts = rng.integers(1, 200, size=(N_SESSIONS, PROMPT_LEN))
    toks = {}
    for sid, prompt in zip(sessions, prompts):
        out, _ = sim.run_process(
            eng.open(sid, prompt[None].astype(np.int32),
                     PROMPT_LEN + DECODE_STEPS + 1))
        toks[sid] = int(np.argmax(out[0]))
    cost = 0.0
    tokens = 0
    last = None
    fed: List[np.ndarray] = []
    for t in range(DECODE_STEPS):
        x = (feed[t] if feed is not None
             else np.asarray([toks[s] for s in sessions], np.int32))
        fed.append(x)
        out, served, c = eng.step(sessions, x)
        cost += c
        tokens += len(served)
        for sid, row in zip(served, out):
            toks[sid] = int(np.argmax(row))
        last = out
    return cost, float(tokens), eng.kv_bytes(), np.asarray(last), fed


def main(report: List[str], smoke: bool = False) -> Dict[str, Any]:
    cfg = get_config("granite-8b").reduced(n_layers=4, d_model=64, vocab=256)
    params = ops_for(cfg).init(cfg, jax.random.PRNGKey(0))

    rows = {}
    feed = None
    # the fused run goes first and records its greedy token feed; the
    # unfused engine replays it, so both decode the same tokens
    for name, kw in (("fused", {}), ("unfused", {"fused": False})):
        sim = Sim(seed=3)
        eng = _build_engine(cfg, params, sim, **kw)
        cost, tokens, cache_bytes, _, fed = _drive(eng, sim, feed)
        if feed is None:
            feed = fed
        rows[name] = {"decode_cost_s": cost, "tokens": tokens,
                      "tokens_per_s": tokens / max(cost, 1e-12),
                      "cache_bytes": cache_bytes,
                      "fused": eng.fused}

    speedup = rows["fused"]["tokens_per_s"] / rows["unfused"]["tokens_per_s"]

    report.append(f"# Decode step: {N_SESSIONS} sessions, "
                  f"{PROMPT_LEN}-token prompts, {DECODE_STEPS} decode steps "
                  f"(granite-8b reduced: L=4 d=64)")
    report.append(f"{'engine':<10}{'tok/s':>12}{'cost_s':>12}"
                  f"{'cache_KiB':>12}")
    for name, r in rows.items():
        report.append(f"{name:<10}{r['tokens_per_s']:>12.0f}"
                      f"{r['decode_cost_s']:>12.2e}"
                      f"{r['cache_bytes'] / 1024:>12.1f}")
    report.append(f"fused speedup: {speedup:.2f}x")

    metrics = {
        "engines": rows,
        "fused_speedup": speedup,
        "gates": {"fused_speedup_min": 2.0},
    }
    if smoke:
        ok = speedup >= 2.0
        report.append(f"smoke: {'OK' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit(
                f"decode_step smoke failed: speedup={speedup:.2f} "
                f"(need >=2)")
    return metrics


if __name__ == "__main__":
    out: List[str] = []
    metrics = main(out, smoke="--kernel-smoke" in sys.argv)
    print("\n".join(out))
    try:
        from benchmarks import _bench
    except ImportError:         # standalone: benchmarks/ itself is on sys.path
        import _bench
    print(f"(wrote {_bench.emit('decode_step', metrics)})")
