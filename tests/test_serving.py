"""Sharded inference over the mesh: pipeline correctness + DHT failover."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fleet import make_fleet
from repro.models import ops_for
from repro.serving.sharded import ShardClient, deploy_sharded


@pytest.fixture(scope="module")
def served():
    cfg = get_config("granite-8b").reduced(n_layers=4, d_model=64, vocab=256)
    ops = ops_for(cfg)
    params = ops.init(cfg, jax.random.PRNGKey(0))
    fleet = make_fleet(9, seed=21, same_region="us")
    sim = fleet.sim
    # 2 shards × 2 replicas on the first 4 peers
    servers = deploy_sharded(fleet.peers[:4], cfg, params, "svc", replicas=2)

    def announce():
        for s in servers:
            yield from s.announce()

    sim.run_process(announce(), until=sim.now + 600)
    return cfg, ops, params, fleet, servers


def test_pipeline_score_matches_local(served):
    cfg, ops, params, fleet, servers = served
    sim = fleet.sim
    client = ShardClient(fleet.peers[-1], cfg, "svc", n_shards=2)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                         0, cfg.vocab), np.int32)

    def run():
        out = yield from client.score(toks)
        return out

    remote = sim.run_process(run(), until=sim.now + 600)
    local, _ = ops.forward(params, cfg, {"tokens": jnp.asarray(toks)})
    np.testing.assert_allclose(remote, np.asarray(local), atol=1e-4, rtol=1e-4)


def test_generation_matches_local_engine(served):
    cfg, ops, params, fleet, servers = served
    sim = fleet.sim
    client = ShardClient(fleet.peers[-2], cfg, "svc", n_shards=2)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 8),
                                         0, cfg.vocab), np.int32)

    def run():
        out = yield from client.generate(toks, 4)
        return out

    remote = sim.run_process(run(), until=sim.now + 600)
    from repro.serving.engine import GenerationEngine
    eng = GenerationEngine(cfg, params, max_len=32)
    local, _ = eng.generate({"tokens": jnp.asarray(toks)}, 4)
    np.testing.assert_array_equal(remote, local)


def test_failover_to_replica_shard(served):
    cfg, ops, params, fleet, servers = served
    sim = fleet.sim
    # kill the first replica of shard 0
    dead = [s for s in servers if s.shard_idx == 0][0]
    dead.stop()
    client = ShardClient(fleet.peers[-1], cfg, "svc", n_shards=2)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (1, 8),
                                         0, cfg.vocab), np.int32)

    def run():
        out = yield from client.score(toks)
        return out

    remote = sim.run_process(run(), until=sim.now + 900)
    local, _ = ops.forward(params, cfg, {"tokens": jnp.asarray(toks)})
    np.testing.assert_allclose(remote, np.asarray(local), atol=1e-4, rtol=1e-4)
    assert client.stats["failovers"] >= 1


@pytest.mark.parametrize("arch", ["minicpm-2b", "granite-8b"])
@pytest.mark.parametrize("n_shards", [1, 2, 3])
def test_init_shard_params_bitwise_equal_to_split(arch, n_shards):
    """Each shard initialised alone equals the same slice of the whole
    init, bit for bit, with a tied embedding (minicpm) and without."""
    from repro.serving.sharded import (init_shard_params, plan_shards,
                                       split_params)
    cfg = get_config(arch).reduced(n_layers=3, d_model=64, vocab=128)
    key = jax.random.PRNGKey(7)
    plan = plan_shards(cfg, n_shards)
    whole = split_params(cfg, ops_for(cfg).init(cfg, key), plan)
    for i in range(n_shards):
        alone = init_shard_params(cfg, key, plan, i)
        assert jax.tree.structure(alone) == jax.tree.structure(whole[i])
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(whole[i])):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_deploy_sharded_round_robin_placement():
    """Servers go round-robin over the devices they are given (one CPU
    device here: every server lands on it); a shard's params exist once
    per device, shared by replicas there, and the tied embedding is one
    array for the first and last shards."""
    cfg = get_config("minicpm-2b").reduced(n_layers=4, d_model=64, vocab=128)
    fleet = make_fleet(4, seed=5, same_region="us")
    dev = jax.devices()[0]
    servers = deploy_sharded(fleet.peers[:4], cfg, None, "place",
                             replicas=2, init_key=jax.random.PRNGKey(0),
                             devices=[dev])
    assert [s.shard_idx for s in servers] == [0, 1, 0, 1]
    for s in servers:
        for a in jax.tree.leaves(s.module.params):
            assert a.devices() == {dev} and a.committed
    assert servers[0].module.params is servers[2].module.params
    assert servers[1].module.params is servers[3].module.params
    assert (servers[1].module.params["embed_out"]
            is servers[0].module.params["embed"])
    # the whole tree, split and placed, gives the same params
    whole = ops_for(cfg).init(cfg, jax.random.PRNGKey(0))
    split = deploy_sharded(fleet.peers[:2], cfg, whole, "place2",
                           devices=[dev])
    for a, b in zip(servers[:2], split):
        for x, y in zip(jax.tree.leaves(a.module.params),
                        jax.tree.leaves(b.module.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(ValueError):
        deploy_sharded(fleet.peers[:2], cfg, whole, "bad",
                       init_key=jax.random.PRNGKey(0))
