"""Sharded inference over the mesh: pipeline correctness + DHT failover."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fleet import make_fleet
from repro.models import ops_for
from repro.serving.sharded import ShardClient, ShardServer, deploy_sharded


def _deploy(seed, fleet_name):
    """A 2 shards × 2 replicas fleet on the first 4 of 9 peers, announced."""
    cfg = get_config("granite-8b").reduced(n_layers=4, d_model=64, vocab=256)
    ops = ops_for(cfg)
    params = ops.init(cfg, jax.random.PRNGKey(0))
    fleet = make_fleet(9, seed=seed, same_region="us")
    sim = fleet.sim
    servers = deploy_sharded(fleet.peers[:4], cfg, params, fleet_name,
                             replicas=2)

    def announce():
        for s in servers:
            yield from s.announce()

    sim.run_process(announce(), until=sim.now + 600)
    return cfg, ops, params, fleet, servers


@pytest.fixture(scope="module")
def served():
    return _deploy(21, "svc")


def _prompts(cfg, seed, n, length=8):
    return [np.asarray(jax.random.randint(jax.random.PRNGKey(seed + i),
                                          (1, length), 0, cfg.vocab),
                       np.int32) for i in range(n)]


def _generate(client, prompts, n_tokens, until=900):
    """Every prompt submitted at once; their tokens, in order."""
    sim = client.node.sim
    return sim.run_process(client.generate_concurrent(
        [{"tokens": p, "n_tokens": n_tokens} for p in prompts]),
        until=sim.now + until)


def _local_tokens(cfg, params, prompt, n_tokens):
    from repro.serving.engine import GenerationEngine
    eng = GenerationEngine(cfg, params, max_len=32)
    local, _ = eng.generate({"tokens": jnp.asarray(prompt)}, n_tokens)
    return np.asarray(local)[0]


def test_pipeline_score_matches_local(served):
    """The logits the client samples its first token from (passed to
    ``on_token``) are the local forward pass's at the prompt's last
    position."""
    cfg, ops, params, fleet, servers = served
    seen = []
    client = ShardClient(fleet.peers[-1], cfg, "svc", n_shards=2,
                         on_token=lambda req, tok, logits: seen.append(logits))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 16),
                                         0, cfg.vocab), np.int32)
    _generate(client, [toks], 1)
    local, _ = ops.forward(params, cfg, {"tokens": jnp.asarray(toks)})
    assert len(seen) == 1
    np.testing.assert_allclose(seen[0], np.asarray(local)[0, -1],
                               atol=1e-4, rtol=1e-4)


def test_generation_matches_local_engine(served):
    cfg, ops, params, fleet, servers = served
    client = ShardClient(fleet.peers[-2], cfg, "svc", n_shards=2)
    (toks,) = _prompts(cfg, 2, 1)
    (remote,) = _generate(client, [toks], 4)
    np.testing.assert_array_equal(remote,
                                  _local_tokens(cfg, params, toks, 4))


def test_finished_requests_leave_no_session_or_page(served):
    """Once requests finish and their closes settle, every shard's engine
    holds no session and no page."""
    cfg, ops, params, fleet, servers = served
    client = ShardClient(fleet.peers[-3], cfg, "svc", n_shards=2)
    out = _generate(client, _prompts(cfg, 40, 5), 6)
    assert all(o is not None and len(o) == 6 for o in out)
    fleet.sim.run(until=fleet.sim.now + 30)
    assert sum(s.engine.stats["admitted"] for s in servers) >= 10
    for s in servers:
        assert not s.engine.by_session, s.shard_idx
        assert s.engine.stats["pages"] == 0, s.shard_idx


def test_shard_server_keeps_one_kv_format(served):
    cfg, ops, params, fleet, servers = served
    with pytest.raises(ValueError):
        ShardServer(fleet.peers[4], cfg, "svc-int8", 0, servers[0].module,
                    kv_dtype="int8")


@pytest.fixture()
def served_one_dead():
    """Its own fleet, with the first replica of shard 0 stopped."""
    deployed = _deploy(23, "svc-dead")
    servers = deployed[-1]
    [s for s in servers if s.shard_idx == 0][0].stop()
    return deployed


def test_failover_to_replica_shard(served_one_dead):
    """Admissions routed to the stopped replica fail over to the live
    one; the tokens are still the local engine's."""
    cfg, ops, params, fleet, servers = served_one_dead
    client = ShardClient(fleet.peers[-1], cfg, "svc-dead", n_shards=2)
    prompts = _prompts(cfg, 3, 3)
    remote = _generate(client, prompts, 4)
    for toks, got in zip(prompts, remote):
        np.testing.assert_array_equal(got,
                                      _local_tokens(cfg, params, toks, 4))
    assert client.stats["failovers"] >= 1
    assert client.stats["failed_sessions"] == 0


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
