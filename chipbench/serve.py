"""Drives one serving cell through the system's normal path.

Set-up builds a simulated mesh of shard peers and one client peer (all
public, so every connection is direct), places the benchmark's weights in
``ShardServer``s on one device, grows each shard's KV page pool to the
most the mix can hold, and warms up every prompt length and block-table
width the mix reaches.  The window then runs closed-loop clients: each is
a sim process that submits a request through ``ShardClient.submit`` and
its next one when that returns.  The path is ``ShardClient`` ->
``InferenceV2Service.open/step`` -> ``BatchEngine._prefill`` /
``_step_fused`` on each pipeline shard, with activations crossing the RPC
hop between shards.  The simulator's network delays are virtual and never
reach the host clock; every time taken here is ``time.perf_counter``.

Per-token stamps come from ``StampedClient``, which overrides the
client's private ``_sample``, ``_finish`` and ``_fail`` hooks (the
system has no public per-token hook yet).
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List

import jax
import numpy as np

from repro.core import tracing
from repro.core.fleet import make_fleet
from repro.serving.batch import BatchEngine
from repro.serving.sharded import (ShardClient, ShardModule, ShardServer,
                                   plan_shards)

from chipbench import record, trace as tr, traffic as tf
from chipbench.timing import Call, CompileClock, Spanned, Timed

#: every peer public: a relayed circuit's upgrade would reset the RPC
#: streams in flight and make the client re-run prefills
PUBLIC = [(None, 1.0)]
FLEET = "bench"
#: virtual seconds the simulator runs between two looks at the host clock
SLICE = 0.02
#: host seconds the window's requests may take, after it, to give their
#: first token (and, while the check lacks finished requests, their last)
DRAIN_S = 120.0


class StampedClient(ShardClient):
    """``ShardClient`` that stamps the host clock at every token it
    samples, through the client's private per-token hook."""

    def __init__(self, *args: Any, **kw: Any):
        super().__init__(*args, **kw)
        self.tracked: Dict[int, record.Request] = {}

    def track(self, done: Any, rec: record.Request) -> None:
        self.tracked[id(done)] = rec

    def _sample(self, req: Any, logits: np.ndarray) -> int:
        tok = super()._sample(req, logits)
        rec = self.tracked.get(id(req.done))
        if rec is not None:
            rec.stamps.append(time.perf_counter())
            rec.tokens.append(tok)
            rec.logits.append(logits)     # kept for the check, not copied
        return tok

    def _finish(self, req: Any, in_active: bool = True) -> None:
        super()._finish(req, in_active)
        rec = self.tracked.get(id(req.done))
        if rec is not None:
            rec.finished = True

    def _fail(self, req: Any) -> None:
        super()._fail(req)
        rec = self.tracked.get(id(req.done))
        if rec is not None:
            rec.failed = True


class Deployment:
    """The system under test for one cell, from weights to client."""

    def __init__(self, config: Dict[str, Any], mix: Dict[str, Any],
                 seed: int, device: Any, family: Any, program: Any):
        sv = config["serving"]
        self.cfg = program.program_config(config)
        self.n_shards = sv["shards"]
        self.plan = plan_shards(self.cfg, self.n_shards)
        if self.plan != family.plan(config["num_hidden_layers"], self.n_shards):
            raise RuntimeError("the weight maker's layer plan is not the system's")
        self.fleet = make_fleet(self.n_shards + 1, seed=seed, nat_mix=PUBLIC)
        self.sim = self.fleet.sim
        with jax.default_device(device):
            parts = family.make_shards(config, seed, self.plan)
        parts = [jax.device_put(p, device) for p in parts]
        jax.block_until_ready(parts)
        self.servers: List[ShardServer] = []
        for i, (lo, hi) in enumerate(self.plan):
            module = ShardModule(self.cfg, parts[i], (lo, hi), is_first=i == 0,
                                 is_last=i == self.n_shards - 1)
            self.servers.append(ShardServer(
                self.fleet.peers[i], self.cfg, FLEET, i, module,
                n_slots=mix["n_slots"], page_size=sv["page_size"],
                kv_dtype={"float32": "fp32"}[sv["kv_dtype"]]))
        del parts

        def announce():
            for s in self.servers:
                yield from s.announce()

        self.sim.run_process(announce())
        # the pool never shrinks: grow it now to the most the mix can hold,
        # so that no window step meets a pool of a new size (a new program)
        most = mix["n_slots"] * tf.max_session_pages(mix, sv["page_size"])
        for s in self.servers:
            pool = s.engine._pool
            pool.free(pool.alloc(most))
        self.client = StampedClient(self.fleet.peers[-1], self.cfg, FLEET,
                                    n_shards=self.n_shards)

    @property
    def engines(self) -> List[BatchEngine]:
        return [s.engine for s in self.servers]

    def request(self, prompt: np.ndarray, n_tokens: int) -> Any:
        """One request run to its end, alone (warm-up)."""
        def wait():
            out = yield self.client.submit(prompt, n_tokens)
            return out
        return self.sim.run_process(wait(), until=self.sim.now + 3600)

    def pool_pages(self) -> List[int]:
        return [e._pool.n_pages for e in self.engines]


def warm_up(dep: Deployment, mix: Dict[str, Any], vocab: int,
            page: int, seed: int) -> None:
    """Every prompt length's prefill on every shard, and every block-table
    width of the fused step, at the pool's steady size."""
    rng = np.random.default_rng([seed, 0x3A4])
    for s, n in tf.warmup_shapes(mix, page):
        out = dep.request(rng.integers(0, vocab, s).astype(np.int32), n)
        if out is None or len(out) != n:
            raise RuntimeError(f"warm-up request ({s}, {n}) failed: "
                               f"{dep.client.stats}")


def instrument(dep: Deployment, config: Dict[str, Any], clock: CompileClock,
               family: Any) -> Dict[str, List[Call]]:
    """Traced runs only: wrap each engine's fused step (argument wait and
    compute wait; the shard's layers and place, the live rows' cached
    lengths, and the family's count of the step's operations and bytes),
    its ``_prefill`` and its ``step`` in named host spans; then turn the
    program's tracer on."""
    calls: Dict[str, List[Call]] = {"fused": [], "prefill": [], "step": []}
    for i, (eng, (lo, hi)) in enumerate(zip(dep.engines, dep.plan)):
        first, last = i == 0, i == dep.n_shards - 1

        def note(params, xb, positions, bt, lengths, *rest,
                 _n=hi - lo, _f=first, _l=last):
            live = [int(v) for v in np.asarray(lengths) if v > 0]
            pb = jax.tree_util.tree_leaves(params)[0].dtype.itemsize
            info = family.fused_step(config, _n, _f, _l, live, pb)
            info.update(lengths=live, n_layers=_n, first=_f, last=_l)
            return info

        eng._fused_apply = Timed(eng._fused_apply, clock, note, "kv_copy",
                                 calls["fused"])
        eng._prefill = Spanned(
            eng._prefill, "prefill", calls["prefill"],
            note=lambda session, slot, x, max_len: {"tokens": x.shape[1]})
        eng.step = Spanned(eng.step, "fused_step", calls["step"])
    tracing.enable()
    return calls


def closed_loop(dep: Deployment, gen: tf.Traffic, clients: int,
                requests: List[record.Request], stop: Callable[[], bool]):
    """``clients`` sim processes, each submitting its next request when
    the last returns, until ``stop()``."""
    def client():
        while not stop():
            prompt, n = gen.next()
            rec = record.Request(prompt, n, time.perf_counter())
            requests.append(rec)
            done = dep.client.submit(prompt, n)
            dep.client.track(done, rec)
            yield done
        return None

    return [dep.sim.process(client()) for _ in range(clients)]


def drive(config: Dict[str, Any], mix: Dict[str, Any], *, seed: int,
          seconds: float, traced: bool, chips: int, peaks: Dict[str, Any],
          t_start: float, family: Any, program: Any,
          log: Callable[[str], None]) -> Dict[str, Any]:
    """Set up, warm up, run the window, and drain it.  Returns the run's
    record, the memory peak and what the check needs; the deployment is
    gone (and its device memory free) when this returns."""
    devices = jax.devices()[:chips]
    clock = CompileClock()
    try:
        dep = Deployment(config, mix, seed, devices[0], family, program)
        page = config["serving"]["page_size"]
        warm_up(dep, mix, config["vocab_size"], page, seed)
        pools = dep.pool_pages()
        log(f"set-up {time.perf_counter() - t_start:.3f} s, of which "
            f"compiles {clock.seconds:.3f} s ({clock.count} programs)")
        calls = instrument(dep, config, clock, family) if traced else {}
        gen = tf.Traffic(mix, config["vocab_size"], seed)
        requests: List[record.Request] = []
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
        if traced:
            jax.profiler.start_trace(trace_dir)
        compiles0 = clock.count
        t0 = time.perf_counter()
        t1 = t0 + seconds
        closed_loop(dep, gen, mix["clients"], requests,
                    lambda: time.perf_counter() >= t1)
        with jax.profiler.TraceAnnotation("window"):
            while time.perf_counter() < t1:
                with jax.profiler.TraceAnnotation("client"):
                    dep.sim.run(until=dep.sim.now + SLICE)
        t_end = time.perf_counter()
        compiles = clock.count - compiles0
        # the window's last requests wait for their first token, and the
        # rest of the window's requests for their last one while fewer have
        # finished than the check samples; the profiler stops after that, so
        # that the seconds it takes to stop fall in no phase of theirs
        due = [r for r in requests if r.submitted <= t1]
        limit = time.perf_counter() + DRAIN_S

        def waiting() -> bool:
            if any(not r.stamps and not r.failed for r in due):
                return True
            done = sum(r.finished for r in due)
            return (done < mix["check_requests"]
                    and any(not (r.finished or r.failed) for r in due))

        while waiting() and time.perf_counter() < limit:
            dep.sim.run(until=dep.sim.now + SLICE)
        if traced:
            jax.profiler.stop_trace()
        tracing.disable()
        for r in due:
            if not r.stamps:
                r.failed = True
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        log(f"window {t_end - t0:.3f} s (nominal {seconds}); compiles in "
            f"window {compiles}; requests {len(requests)}; pool pages "
            f"{pools} -> {dep.pool_pages()}; client {dep.client.stats}; "
            f"engines {[e.stats for e in dep.engines]}")
        if dep.pool_pages() != pools:
            raise RuntimeError("the KV pool grew inside the window")
        run = record.Run(config=config, traffic=mix, chips=chips,
                         peaks=peaks, window=(t0, t1),
                         setup_s=t0 - t_start, requests=requests, calls=calls,
                         spans=tracing.drain(), family=family)
        del dep, gen
        gc.collect()
        log("device bytes in use after the deployment is freed: "
            f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in devices]}")
        if traced:
            run.trace = tr.extract(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            log(tr.summary(run.trace))
        return {"run": run, "peak": peak, "compiles": compiles}
    finally:
        tracing.disable()
        clock.close()
