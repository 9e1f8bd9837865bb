"""Sharded inference over the mesh (paper Fig. 1-4): pipeline throughput,
per-token latency, and failover cost when a shard dies mid-service.

``main_serving`` benchmarks the continuous-batching plane: N concurrent
clients against a 2-shard × 2-replica fleet, tokens/s of one request at
a time vs all of them batched, p50/p95 request latency, one provider
killed mid-run (must lose zero sessions), and a pressure-spawned
hot-shard replica.  Emits ``BENCH_serving.json``; ``--serve-smoke`` runs the
reduced gating variant used by CI.
"""

from __future__ import annotations

from typing import Any, Dict, Generator, List

import jax
import numpy as np

from repro.configs import get_config
from repro.core.fleet import make_fleet
from repro.models import ops_for
from repro.serving.pressure import PressureMonitor
from repro.serving.sharded import ShardClient, deploy_sharded, serve_fleet

try:
    from . import _bench
except ImportError:         # standalone: benchmarks/ itself is on sys.path
    import _bench


def main(report: List[str]) -> Dict[str, Any]:
    cfg = get_config("granite-8b").reduced(n_layers=4, d_model=128, vocab=512)
    ops = ops_for(cfg)
    params = ops.init(cfg, jax.random.PRNGKey(0))
    fleet = make_fleet(9, seed=99, same_region="us")
    sim = fleet.sim
    servers = deploy_sharded(fleet.peers[:4], cfg, params, "bench",
                             replicas=2)

    def announce() -> Generator:
        for s in servers:
            yield from s.announce()

    sim.run_process(announce(), until=sim.now + 600)
    client = ShardClient(fleet.peers[-1], cfg, "bench", n_shards=2)
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab),
        np.int32)

    def generate(n_tokens: int) -> Generator:
        t0 = sim.now
        out = yield from client.generate_concurrent(
            [{"tokens": t, "n_tokens": n_tokens} for t in toks])
        return out, sim.now - t0

    out, t_gen = sim.run_process(generate(16), until=sim.now + 3600)
    per_tok = t_gen / 16
    report.append("# Sharded inference (2 shards × 2 replicas, reduced model)")
    report.append(f"prefill+16 decode steps: {t_gen:.3f}s "
                  f"({per_tok*1000:.1f} ms/token, batch=4)")

    # failover: kill shard-0 replica used so far, measure next-token latency
    dead = [s for s in servers if s.shard_idx == 0][0]
    dead.stop()
    t0 = sim.now

    def one_more() -> Generator:
        out = yield from client.generate_concurrent(
            [{"tokens": t, "n_tokens": 1} for t in toks])
        return out

    sim.run_process(one_more(), until=sim.now + 3600)
    failover_ms = (sim.now - t0) * 1000
    report.append(f"failover token (shard replica killed): "
                  f"{failover_ms:.1f} ms "
                  f"(failovers={client.stats['failovers']})")
    return {"gen_time_s": t_gen, "ms_per_token": per_tok * 1000,
            "failover_ms": failover_ms,
            "failovers": client.stats["failovers"]}


def main_serving(report: List[str], smoke: bool = False) -> Dict[str, Any]:
    """Continuous-batching serving benchmark (BENCH_serving.json)."""
    n_clients = 32 if smoke else 104
    n_tokens = 24 if smoke else 16
    seq_probe = 4 if smoke else 8
    kill_at = 0.3 if smoke else 0.5
    stagger = 0.01
    n_slots = 8

    cfg = get_config("granite-8b").reduced(n_layers=4, d_model=64, vocab=256)
    ops = ops_for(cfg)
    params = ops.init(cfg, jax.random.PRNGKey(0))
    fleet = make_fleet(12, seed=7, same_region="us")
    sim = fleet.sim
    servers = sim.run_process(
        serve_fleet(fleet.peers[:4], cfg, params, "bench", replicas=2,
                    n_slots=n_slots),
        until=sim.now + 900)
    prompts = [np.asarray(
        jax.random.randint(jax.random.PRNGKey(100 + i), (1, 8), 0, cfg.vocab),
        np.int32) for i in range(8)]

    # -- sequential baseline: same fleet, one request at a time -------------
    seq_client = ShardClient(fleet.peers[-1], cfg, "bench", n_shards=2)

    def sequential() -> Generator:
        t0 = sim.now
        for i in range(seq_probe):
            yield seq_client.submit(prompts[i % len(prompts)], n_tokens)
        return sim.now - t0

    seq_time = sim.run_process(sequential(), until=sim.now + 3600)
    seq_tps = seq_probe * n_tokens / seq_time

    # -- batched: N concurrent clients, provider kill + pressure monitor ----
    client = ShardClient(fleet.peers[-2], cfg, "bench", n_shards=2)
    mon = PressureMonitor(fleet.peers[6], cfg, "bench", hot_occupancy=0.5,
                          sustain=2, interval=0.25, max_replicas=3,
                          n_slots=n_slots)
    sim.process(mon.run())

    latencies: List[float] = []
    killed: List[Any] = []

    def one_client(i: int) -> Generator:
        yield sim.timeout(i * stagger)
        t0 = sim.now
        ev = client.submit(prompts[i % len(prompts)], n_tokens)
        out = yield ev
        if out is not None:
            latencies.append(sim.now - t0)

    def killer() -> Generator:
        yield sim.timeout(kill_at)  # mid-run: admissions have landed
        busy = [s for s in servers
                if s.alive and s.shard_idx == 0 and s.engine.slots_used > 0]
        if busy:
            busy[0].stop()
            killed.append(busy[0])

    def batched() -> Generator:
        t0 = sim.now
        procs = [sim.process(one_client(i)) for i in range(n_clients)]
        sim.process(killer())
        for p in procs:
            yield p
        return sim.now - t0

    bat_time = sim.run_process(batched(), until=sim.now + 3600)
    # grace: a spawn decision taken on the last hot tick still needs sim
    # time to fetch the shard params off the content plane and announce;
    # with the load generators gone this same window is the cold drain —
    # sustained-cold detection retires the monitor-spawned replica and
    # the serving plane returns to its deployed baseline
    sim.run(until=sim.now + 30)
    mon.stop()
    replica_sets = {shard: mon.replica_count(shard) for shard in (0, 1)}
    bat_tps = n_clients * n_tokens / bat_time
    lat = np.asarray(sorted(latencies))
    p50 = float(lat[int(0.50 * (len(lat) - 1))]) if len(lat) else float("nan")
    p95 = float(lat[int(0.95 * (len(lat) - 1))]) if len(lat) else float("nan")

    metrics: Dict[str, Any] = {
        "smoke": smoke,
        "fleet": {"shards": 2, "replicas": 2, "n_slots": n_slots},
        "n_clients": n_clients,
        "n_tokens": n_tokens,
        "sequential_tokens_per_s": seq_tps,
        "batched_tokens_per_s": bat_tps,
        "speedup": bat_tps / seq_tps,
        "latency_p50_s": p50,
        "latency_p95_s": p95,
        "completed": client.stats["completed"],
        "failed_sessions": client.stats["failed_sessions"],
        "sessions_migrated": client.stats["sessions_migrated"],
        "failovers": client.stats["failovers"],
        "provider_killed": bool(killed),
        "replicas_spawned": mon.stats["spawned"],
        "replicas_retired": mon.stats["retired"],
        "monitor_replicas_live": len(mon.spawned),
        # deployed baseline is 2 replicas per shard; after the cold drain
        # every monitor-spawned replica must have left the replica set
        "replica_sets_after_drain": replica_sets,
        "slots_back_to_baseline": all(c == 2 for c in replica_sets.values()),
        "pressure": mon.stats,
    }
    report.append(f"# Serving: {n_clients} concurrent clients, "
                  f"2 shards x 2 replicas, {n_slots} slots/replica")
    report.append(f"sequential: {seq_tps:8.1f} tok/s "
                  f"({seq_probe} requests probed)")
    report.append(f"batched:    {bat_tps:8.1f} tok/s "
                  f"({metrics['speedup']:.1f}x, "
                  f"p50={p50*1000:.0f}ms p95={p95*1000:.0f}ms)")
    report.append(f"provider killed mid-run: {bool(killed)}  "
                  f"failed={metrics['failed_sessions']} "
                  f"migrated={metrics['sessions_migrated']}")
    report.append(f"pressure: spawned {mon.stats['spawned']} replica(s) "
                  f"on hot shards, retired {mon.stats['retired']} after "
                  f"the cold drain (replica sets: {replica_sets})")
    return metrics


if __name__ == "__main__":
    import sys
    out: List[str] = []
    if "--serve-smoke" in sys.argv[1:]:
        metrics = main_serving(out, smoke=True)
        _bench.emit("serving_smoke", metrics)
        print("\n".join(out))
        assert metrics["speedup"] >= 3.0, \
            f"batching gain {metrics['speedup']:.2f}x < 3x"
        assert metrics["provider_killed"], "no provider was killed mid-run"
        assert metrics["failed_sessions"] == 0, \
            f"{metrics['failed_sessions']} sessions failed after provider kill"
        assert metrics["replicas_spawned"] >= 1, "pressure spawned no replica"
        assert metrics["replicas_retired"] >= 1, \
            "cold drain retired no replica"
        assert metrics["monitor_replicas_live"] == 0, \
            "monitor still holds live replicas after the drain"
        assert metrics["slots_back_to_baseline"], \
            f"replica sets never returned to baseline: " \
            f"{metrics['replica_sets_after_drain']}"
        print("smoke: OK")
    else:
        main(out)
        metrics = main_serving(out)
        _bench.emit("serving", metrics)
        print("\n".join(out))
