#!/usr/bin/env python3
"""Bring-up smoke run of the system's chip hot paths at published widths.

    python3 chip_smoke.py               # one chip: kernels, serve, train
    python3 chip_smoke.py --four-chips  # four chips: granite-8b, 4 shards

Phases (one process; no phase's failure is caught):

* ``kernels`` — the paged decode kernel, flash
  attention and MoE gating, compiled for the chip, against their jnp
  references at minicpm-2b head layouts and qwen2-moe-a2.7b routing.
* ``serve``  — minicpm-2b at its published shape (40 layers, fp32, random
  weights from a seed) in 2 pipeline shards on 2 simulated peers, so
  activations cross an RPC hop.  Concurrent greedy requests go through
  ``ShardClient.generate_concurrent`` -> ``InferenceV2Service`` ->
  ``BatchEngine``'s fused paged decode; every served token is checked
  against a plain staged forward pass in fp32 at highest matmul precision.
* ``train``  — two ``CollabWorker``s at minicpm-2b widths run one DiLoCo
  round of 2 inner AdamW steps; the round must close with identical outer
  digests.  Depth and vocabulary are cut so that both workers' optimizer
  state plus one step's new state fit on one chip; the cut is printed.
* ``--four-chips`` runs only ``four_chip_serve``: granite-8b in 4 pipeline
  shards, one ``ShardServer`` per device, each shard initialised on its
  own device, against a staged forward on the same devices.

Times, bytes and peaks printed on the way are bring-up observations, not
benchmark numbers.  The last line of standard output is one JSON object
naming the device.  Without a TPU the script exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import statistics
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.fleet import make_fleet  # noqa: E402
from repro.data import make_batch_iterator  # noqa: E402
from repro.kernels.flash_attention import flash_attention_bhsd  # noqa: E402
from repro.kernels.moe_gating import moe_gating_tokens  # noqa: E402
from repro.kernels.paged_attention import (paged_attention_jnp,  # noqa: E402
                                           paged_attention_pallas)
from repro.kernels.ref import attention_ref, moe_gating_ref  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.models import decoder  # noqa: E402
from repro.models.common import rms_norm  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.optim import cosine_schedule  # noqa: E402
from repro.serving.sharded import (ShardClient, deploy_sharded,  # noqa: E402
                                   plan_shards)
from repro.train import train_state_init  # noqa: E402
from repro.train.collab import CollabConfig, CollabWorker  # noqa: E402

#: served token's reference logit may sit this many standard deviations of
#: the reference row below the row's max.  The served path runs fp32
#: matmuls at the backend's default precision (bf16 passes on a TPU), the
#: reference at "highest"; that rounding moves logits by a few hundredths
#: of a standard deviation, while a token from a wrong cache, position or
#: weight sits whole standard deviations below the max of ~1e5 logits.
LOGIT_TOL = 0.1
#: kernels vs their jnp references (outputs are O(1)): admits bf16 MXU
#: passes inside the kernels, refuses a wrong mask, page or scale
KERNEL_TOL = {"float32": 2e-2, "bfloat16": 6e-2}

#: every peer public, so every connection is direct: upgrading a relayed
#: circuit resets the RPC streams in flight on it, and the session
#: migration that follows would only re-run a prefill
PUBLIC = [(None, 1.0)]

#: minicpm-2b depth for two trainers on one chip (see train_config)
TRAIN_LAYERS = 2
TRAIN_SEQ = 256
TRAIN_BATCH = 2


def _log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Sums JAX's compile events (trace, lowering, backend compile or cache
    read) while installed; ``count`` tells a call that compiled from one
    that did not.  Only lowering and backend compiles count: a trace event
    also fires, taking no time, on a call that hits the jit cache."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_: Any) -> None:
        if event in self.EVENTS:
            self.seconds += secs
            self.count += event != self.EVENTS[0]

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class Timed:
    """Wraps a jitted function.  Each call first waits for its arguments
    (host-to-device copies still in flight) and then for its outputs
    (``block_until_ready``); both wall times are kept when no compile
    happened inside the call."""

    def __init__(self, fn: Callable, clock: CompileClock):
        self.fn = fn
        self.clock = clock
        self.steady: List[float] = []
        self.input_wait: List[float] = []

    def __call__(self, *args: Any) -> Any:
        n = self.clock.count
        t0 = time.perf_counter()
        jax.block_until_ready(args)
        t1 = time.perf_counter()
        out = jax.block_until_ready(self.fn(*args))
        if self.clock.count == n:
            self.input_wait.append(t1 - t0)
            self.steady.append(time.perf_counter() - t1)
        return out

    def median_ms(self, which: str = "steady") -> Optional[float]:
        xs = getattr(self, which)
        return 1e3 * statistics.median(xs) if xs else None


def memory_line(devices: Sequence[Any]) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:in_use={st.get('bytes_in_use')},"
                     f"peak={st.get('peak_bytes_in_use')}")
    return "device bytes " + " ".join(parts)


def check(ok: Any, what: str) -> None:
    """A failed check ends the run (an ``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _close(got: Any, want: Any, tol: float, what: str) -> float:
    err = float(np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(want, np.float32))))
    check(np.isfinite(err) and err <= tol, f"{what}: max |err| {err} > {tol}")
    return err


# ---------------------------------------------------------------- kernels
def phase_kernels(attn_cfg: ModelConfig, moe_cfg: ModelConfig, *,
                  interpret: bool = False, seq: int = 1024, slots: int = 8,
                  page: int = 32, table_pages: int = 8,
                  seed: int = 0) -> Dict[str, float]:
    """Each kernel compiled (or, for CPU tests, interpreted) against its
    jnp reference at ``attn_cfg``'s head layout and ``moe_cfg``'s router."""
    H, Hk, hd = attn_cfg.n_heads, attn_cfg.n_kv_heads, attn_cfg.hd
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    errs: Dict[str, float] = {}

    # paged decode over an fp32 pool
    P = slots * table_pages + 3
    q, kn, vn = normal(slots, H, hd), normal(slots, Hk, hd), normal(slots, Hk, hd)
    kp, vp = normal(P, page, Hk, hd), normal(P, page, Hk, hd)
    bt = jnp.asarray(rng.permutation(P)[:slots * table_pages]
                     .reshape(slots, table_pages), jnp.int32)
    lengths = jnp.asarray(rng.integers(0, table_pages * page, slots),
                          jnp.int32)
    paged = jax.jit(lambda *a: paged_attention_pallas(*a, interpret=interpret))
    with jax.default_matmul_precision("highest"):
        ref32 = paged_attention_jnp(q, kp, vp, bt, lengths, kn, vn)
    errs["paged_fp32"] = _close(paged(q, kp, vp, bt, lengths, kn, vn), ref32,
                                KERNEL_TOL["float32"], "paged fp32")

    # flash attention, causal, at the config's head width
    for dt in (jnp.float32, jnp.bfloat16):
        qkv = [normal(1, H, seq, hd).astype(dt) for _ in range(3)]
        flash = jax.jit(lambda a, b, c: flash_attention_bhsd(
            a, b, c, causal=True, interpret=interpret))
        with jax.default_matmul_precision("highest"):
            want = attention_ref(*qkv, causal=True)
        name = jnp.dtype(dt).name
        errs[f"flash_{name}"] = _close(flash(*qkv), want, KERNEL_TOL[name],
                                       f"flash {name}")

    # MoE router gating: published expert count and experts per token
    E, K = moe_cfg.n_experts, moe_cfg.moe_top_k
    logits = normal(512, E) * 2
    w, idx, probs = jax.jit(lambda x: moe_gating_tokens(
        x, K, interpret=interpret))(logits)
    wr, ir, pr = moe_gating_ref(logits, K)
    errs["moe_gating_probs"] = _close(probs, pr, 1e-5, "gating probs")
    errs["moe_gating_weights"] = _close(w, wr, 1e-5, "gating weights")
    check(np.array_equal(np.asarray(idx), np.asarray(ir)), "gating experts")
    return errs


# ------------------------------------------------------------------ serve
def _device_of(tree: Any) -> Any:
    return next(iter(jax.tree.leaves(tree)[0].devices()))


def _ref_stage(cfg: ModelConfig, lo: int, first: bool, last: bool,
               p: Dict[str, Any], x: jax.Array, rows: jax.Array) -> jax.Array:
    """One pipeline stage of the plain dense forward (``decoder.forward``'s
    own embedding, block scan and head); the last stage returns logits at
    sequence positions ``rows`` only."""
    if first:
        x = jnp.take(p["embed"], x, axis=0)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, _ = decoder.apply_blocks(cfg, p["blocks"], x, positions,
                                first_layer=lo)
    if last:
        x = rms_norm(x[:, rows], p["final_norm"], cfg.norm_eps)
        head = p["lm_head"] if "lm_head" in p else p["embed_out"].T
        x = x @ head
    return x


_ref_stage_jit = jax.jit(_ref_stage, static_argnums=(0, 1, 2, 3))


def staged_reference_logits(cfg: ModelConfig, parts: List[Dict[str, Any]],
                            tokens: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Plain fp32 forward at highest matmul precision, stage by stage on
    each stage's own device (one copy of the weights, as served)."""
    plan = plan_shards(cfg, len(parts))
    x: Any = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        for i, (p, (lo, _)) in enumerate(zip(parts, plan)):
            x = jax.device_put(x, _device_of(p))
            x = _ref_stage_jit(cfg, lo, i == 0, i == len(parts) - 1, p, x,
                               jnp.asarray(rows, jnp.int32))
    return np.asarray(x, np.float32)


def phase_serve(cfg: ModelConfig, *, n_shards: int = 2, n_requests: int = 4,
                prompt_len: int = 128, new_tokens: int = 16,
                devices: Optional[List[Any]] = None, seed: int = 0,
                tag: str = "serve") -> Dict[str, Any]:
    """Serve ``n_requests`` concurrent greedy requests through the sharded
    plane and check every served token against the staged reference."""
    devices = list(devices or jax.devices()[:1])
    clock = CompileClock()
    try:
        fleet = make_fleet(n_shards + 1, seed=seed, nat_mix=PUBLIC)
        sim = fleet.sim
        before = sum((d.memory_stats() or {}).get("bytes_in_use", 0)
                     for d in devices)
        t0 = time.perf_counter()
        servers = deploy_sharded(fleet.peers[:n_shards], cfg, None, tag,
                                 n_slots=n_requests,
                                 init_key=jax.random.PRNGKey(seed),
                                 devices=devices)
        jax.block_until_ready([s.module.params for s in servers])
        unique = {id(a): a for s in servers
                  for a in jax.tree.leaves(s.module.params)}
        param_bytes = sum(a.nbytes for a in unique.values())
        after = sum((d.memory_stats() or {}).get("bytes_in_use", 0)
                    for d in devices)
        _log(tag, f"{cfg.name}: {n_shards} shards on devices "
             f"{[_device_of(s.module.params).id for s in servers]}, "
             f"param bytes {param_bytes}, device bytes added by deploy "
             f"{after - before}, init s {time.perf_counter() - t0:.3f}")
        check(after - before < 2 * param_bytes,
              "params exist more than once on the device")
        timers = []
        for s in servers:
            eng = s.engine
            eng._fused_apply = Timed(eng._fused_apply, clock)
            timers.append(eng._fused_apply)

        def announce():
            for s in servers:
                yield from s.announce()

        sim.run_process(announce())
        client = ShardClient(fleet.peers[-1], cfg, tag, n_shards=n_shards)
        prompts = np.random.default_rng(seed).integers(
            0, cfg.vocab, (n_requests, prompt_len)).astype(np.int32)
        t0 = time.perf_counter()
        outs = sim.run_process(client.generate_concurrent(
            [{"tokens": p, "n_tokens": new_tokens} for p in prompts]),
            until=sim.now + 3600)
        wall = time.perf_counter() - t0
        check(all(o is not None and len(o) == new_tokens for o in outs),
              f"requests failed: {client.stats}")
        served = np.stack(outs)

        # each generated token t was predicted at position prompt_len-1+t
        full = np.concatenate([prompts, served], axis=1)
        rows = prompt_len - 1 + np.arange(new_tokens)
        ref = staged_reference_logits(cfg, [s.module.params for s in servers],
                                      full, rows)
        picked = np.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
        gap = ref.max(-1) - picked
        tol = LOGIT_TOL * ref.std(-1)
        worst = float(np.max(gap / ref.std(-1)))
        check(np.all(gap <= tol),
              f"served token below the reference max by {worst:.4f} sd")
        argmax_agree = float(np.mean(ref.argmax(-1) == served))
        steady = [t.median_ms() for t in timers]
        _log(tag, f"{n_requests} requests x {new_tokens} tokens completed "
             f"(prompt {prompt_len}); worst gap {worst:.6f} sd "
             f"(limit {LOGIT_TOL}), reference argmax agreement "
             f"{argmax_agree:.4f}")
        waits = [t.median_ms("input_wait") for t in timers]
        _log(tag, f"bring-up observation: compile s {clock.seconds:.3f}, "
             f"steady decode-step ms per shard {steady} "
             f"(n={[len(t.steady) for t in timers]}), waiting for its "
             f"inputs (KV pool copy) ms {waits}, serve wall s "
             f"{wall:.3f}, {memory_line(devices)}")
        return {"worst_gap_sd": worst, "argmax_agree": argmax_agree,
                "param_bytes": param_bytes, "compile_s": clock.seconds,
                "step_ms": steady, "client": dict(client.stats)}
    finally:
        clock.close()


# ------------------------------------------------------------------ train
def phase_train(cfg: ModelConfig, *, seq: int = TRAIN_SEQ,
                batch: int = TRAIN_BATCH, inner_steps: int = 2,
                seed: int = 0) -> Dict[str, Any]:
    """Two collab workers, one outer round of ``inner_steps`` AdamW steps:
    the round must close on both with the same outer digest."""
    clock = CompileClock()
    try:
        fleet = make_fleet(3, seed=seed, nat_mix=PUBLIC, same_region="us")
        sim = fleet.sim
        sched = cosine_schedule(1e-3, 1, 100)
        ccfg = CollabConfig(inner_steps=inner_steps, settle=0.5)
        workers = []
        for i in range(2):
            data = make_batch_iterator(cfg.vocab, seq, 2 * batch,
                                       n_shards=2, shard=i, seed=seed)
            w = CollabWorker(fleet.peers[i], cfg,
                             train_state_init(cfg, jax.random.PRNGKey(seed)),
                             sched, data, "smoke", collab=ccfg,
                             step_seconds=0.2)
            w.step_fn = Timed(w.step_fn, clock)
            workers.append(w)
        t0 = time.perf_counter()
        procs = [sim.process(w.run(1)) for w in workers]
        sim.run(until=sim.now + 600)
        wall = time.perf_counter() - t0
        for p in procs:
            check(p.triggered and not p.failed,
                  f"worker failed: {getattr(p, 'value', None)}")
        check(all(w.outer_round == 1 and w.stats["rounds_closed"] == 1
                  for w in workers), f"round open: {[w.stats for w in workers]}")
        digests = {w.outer_digest() for w in workers}
        check(len(digests) == 1, "outer state forked")
        losses = [h["loss"] for w in workers for h in w.history]
        check(len(losses) == 2 * inner_steps
              and all(math.isfinite(v) for v in losses), f"losses {losses}")
        steady = [w.step_fn.median_ms() for w in workers]
        _log("train", f"round closed on 2 workers, digest "
             f"{digests.pop()[:16]}, losses {losses}, wire/dense bytes "
             f"{workers[0].stats['wire_bytes']}/"
             f"{workers[0].stats['dense_bytes']}")
        _log("train", f"bring-up observation: compile s {clock.seconds:.3f}, "
             f"steady inner-step ms per worker {steady}, round wall s "
             f"{wall:.3f}, {memory_line(jax.devices()[:1])}")
        return {"losses": losses, "compile_s": clock.seconds,
                "step_ms": steady}
    finally:
        clock.close()


def train_config() -> ModelConfig:
    """minicpm-2b at published widths and vocabulary, cut in depth so two
    workers' params + AdamW moments and one step's new state fit one
    16 GiB chip.  The inner step donates nothing, so that is 9 copies of
    the params at 4 bytes each plus gradients and activations: 13.7 GiB
    at 2 layers by the compiler's memory analysis, 15.9 GiB at 3."""
    return dataclasses.replace(get_config("minicpm-2b"),
                               n_layers=TRAIN_LAYERS)


# ------------------------------------------------------------------- main
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded serving path")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devices[0].platform!r} ({len(devices)} device(s))",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    cache = configure_compile_cache()
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(devices)}; compile cache {cache}", flush=True)

    if args.four_chips:
        phase_serve(get_config("granite-8b"), n_shards=4,
                    devices=devices[:4], tag="four_chip_serve")
    else:
        errs = phase_kernels(get_config("minicpm-2b"),
                             get_config("qwen2-moe-a2.7b"))
        _log("kernels", f"max |err| vs jnp references {errs}")
        phase_serve(get_config("minicpm-2b"))
        gc.collect()
        _log("serve", f"after the phase: {memory_line(devices[:1])}")
        cfg = train_config()
        full = get_config("minicpm-2b")
        _log("train", "reduced: " + json.dumps(
            {"n_layers": [full.n_layers, cfg.n_layers]}))
        phase_train(cfg)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
