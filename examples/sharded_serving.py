"""Sharded AI inference over the Lattica DHT (paper Fig. 1, Scenario 4).

A 4-layer model is pipeline-split into 2 shards × 2 replicas, placed on
mesh peers (some behind NATs).  A client resolves shard providers through
the DHT, streams activations through the pipeline, and — when we kill a
shard server mid-service — fails over to the replica transparently.

    PYTHONPATH=src python examples/sharded_serving.py
"""

import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import get_config
from repro.core.fleet import make_fleet
from repro.models import ops_for
from repro.serving.sharded import ShardClient, deploy_sharded


def main():
    cfg = get_config("granite-8b").reduced(n_layers=4, d_model=128, vocab=512)
    ops = ops_for(cfg)
    params = ops.init(cfg, jax.random.PRNGKey(0))
    print(f"model: granite-8b family (reduced), "
          f"{sum(x.size for x in jax.tree.leaves(params))/1e6:.2f}M params")

    fleet = make_fleet(9, seed=99)
    sim = fleet.sim
    hosts = fleet.peers[:4]
    servers = deploy_sharded(hosts, cfg, params, "demo", replicas=2)
    print("placement:")
    for s in servers:
        print(f"  shard {s.shard_idx} (layers {s.module.lo}-{s.module.hi-1}"
              f"{' +embed' if s.module.is_first else ''}"
              f"{' +head' if s.module.is_last else ''}) on "
              f"{s.node.host.name} [{s.node.transport.reachability}]")

    def announce():
        for s in servers:
            yield from s.announce()

    sim.run_process(announce())

    client = ShardClient(fleet.peers[-1], cfg, "demo", n_shards=2)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab), np.int32)

    def generate(n):
        t0 = sim.now
        out = yield client.submit(prompt, n)
        return out, sim.now - t0

    out, dt = sim.run_process(generate(8))
    print(f"\ngenerated (pipeline): {out.tolist()}  [{dt:.2f}s sim, "
          f"{dt/8*1000:.0f} ms/token]")

    print("\nkilling the serving shard-0 replica mid-generation ...")

    shard0 = [s for s in servers if s.shard_idx == 0]

    def work(s):
        return s.engine.stats["admitted"] + s.engine.stats["steps"]

    def generate_with_kill(n):
        t0 = sim.now
        before = {id(s): work(s) for s in shard0}
        gen = sim.process(generate(n))
        # let the admission and a few decode steps land on shard 0
        while sum(work(s) - before[id(s)] for s in shard0) < 4:
            yield sim.timeout(0.05)
        victim = max((s for s in shard0 if s.alive),
                     key=lambda s: work(s) - before[id(s)])
        victim.stop()
        print(f"  killed {victim.node.host.name} mid-run")
        out, _ = yield gen
        return out, sim.now - t0

    out2, dt2 = sim.run_process(generate_with_kill(8), until=sim.now + 3600)
    print(f"generated (after failover): {out2.tolist()}  [{dt2:.2f}s sim]")
    print(f"client stats: {client.stats}")
    # the dead replica's sessions migrated (prefill replayed on the
    # survivor) and/or the retried call failed over — and greedy output
    # is unchanged by where it was computed
    assert client.stats["failovers"] + client.stats["sessions_migrated"] >= 1
    assert out2.tolist() == out.tolist()
    print("transparent DHT failover verified.")


if __name__ == "__main__":
    main()
