"""The host-time tracer (repro.core.tracing) and the serving plane's spans:
off it records nothing; on, a request's phases tile submit -> first token,
the decode rounds' spans carry the engine's counters, and the simulation
runs exactly as with it off."""

import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tracing
from repro.core.fleet import make_fleet
from repro.core.simnet import Sim
from repro.models import ops_for
from repro.serving.sharded import ShardClient, deploy_sharded

#: the phases of a request up to its first token, in the order a hop runs them
REQUEST_PHASES = ("client.queue", "rpc.open.send", "engine.admit_wait",
                  "engine.prefill", "rpc.cpu_charge", "rpc.open.reply",
                  "client.sample")


@pytest.fixture
def tracer():
    tracing.drain()
    yield tracing
    tracing.disable()
    tracing.drain()


@pytest.fixture(scope="module")
def model():
    cfg = get_config("granite-8b").reduced(n_layers=2, d_model=32, vocab=128)
    return cfg, ops_for(cfg).init(cfg, jax.random.PRNGKey(3))


def serve(model, sim=None, n_slots=2):
    """A 2-shard pipeline on a public fleet, announced, and a client."""
    cfg, params = model
    fleet = make_fleet(3, seed=5, sim=sim, same_region="us",
                       nat_kinds=[None] * 3)
    servers = deploy_sharded(fleet.peers[:2], cfg, params, "trace",
                             n_slots=n_slots)

    def announce():
        for s in servers:
            yield from s.announce()

    fleet.sim.run_process(announce(), until=fleet.sim.now + 600)
    client = ShardClient(fleet.peers[-1], cfg, "trace", n_shards=2)
    return fleet.sim, servers, client


def generate(sim, client, prompts, n_tokens):
    def run():
        out = yield from client.generate_concurrent(
            [dict(tokens=p, n_tokens=n_tokens) for p in prompts])
        return out
    return sim.run_process(run(), until=sim.now + 900)


def prompts(cfg, n, length=12):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab, (1, length)).astype(np.int32)
            for _ in range(n)]


def test_off_records_nothing_and_allocates_no_span(tracer):
    assert not tracer.TRACER.on
    assert tracer.span("engine.step", rows=3) is tracer.NOOP
    assert tracer.span("kv.upload") is tracer.NOOP
    assert tracer.begin("request", request=1) is None
    assert tracer.start_flow(request=1) is None
    with tracer.span("engine.step") as sp:
        sp.set(rows=1)
    tracer.phase(("peer", 1), "rpc.cpu_charge")
    tracer.end(None)
    assert tracer.drain() == []


def test_sync_spans_nest_and_flows_tile(tracer):
    tracer.enable()
    req = tracer.begin("request", request=7)
    flow = tracer.start_flow(request=7, parent=req)
    tracer.link(flow, ("peer", 1))
    tracer.phase(flow, "client.queue")
    tracer.phase(("peer", 1), "engine.admit_wait")
    with tracer.span("engine.prefill", flow=("peer", 1)):
        with tracer.span("kv.write_prefill"):
            pass
    tracer.phase(("peer", 1), "rpc.cpu_charge", virtual_s=0.5)
    tracer.close_flow(flow)
    tracer.phase(("peer", 1), "rpc.open.reply")     # closed: not recorded
    tracer.end(req, tokens=1)
    recs = {r.name: r for r in tracer.drain()}
    assert set(recs) == {"request", "client.queue", "engine.admit_wait",
                         "engine.prefill", "kv.write_prefill",
                         "rpc.cpu_charge"}
    assert recs["kv.write_prefill"].parent_id == recs["engine.prefill"].span_id
    assert recs["kv.write_prefill"].request_id == 7
    chain = [recs[n] for n in ("client.queue", "engine.admit_wait",
                               "engine.prefill", "rpc.cpu_charge")]
    assert all(r.parent_id == recs["request"].span_id for r in chain)
    assert all(a.t1_ns == b.t0_ns for a, b in zip(chain, chain[1:]))
    assert recs["rpc.cpu_charge"].attrs == {"virtual_s": 0.5}
    assert recs["request"].attrs == {"tokens": 1}


def test_records_are_capped_and_disable_forgets_open_flows(tracer):
    t = tracing.Tracer(cap=2)
    t.enable()
    for _ in range(5):
        with t.span("client.sample"):
            pass
    assert len(t.drain()) == 2 and t.dropped == 3
    flow = t.start_flow(request=1)
    t.phase(flow, "client.queue")
    t.disable()
    t.enable()
    t.phase(flow, "rpc.open.send")
    t.close_flow(flow)
    assert t.drain() == []


def test_request_phases_tile_submit_to_first_token(model, tracer):
    """4 requests on 2 slots: each request's phases run from submit to the
    end of its first token's sampling, back to back, and sum to the
    first-token wait the client's hook sees."""
    cfg, _ = model
    sim, servers, client = serve(model)
    generate(sim, client, prompts(cfg, 2), 2)            # compile, dial
    before = [dict(s.engine.stats) for s in servers]
    seen = []
    client.on_token = lambda req, tok, logits: seen.append(
        (req.rid, tok, time.perf_counter()))
    ps = prompts(cfg, 4)

    def run():
        submitted = {}
        events = []
        for p in ps:
            t = time.perf_counter()
            ev = client.submit(p, 6)
            submitted[id(ev)] = t
            events.append(ev)
        outs = []
        for ev in events:
            outs.append((yield ev))
        return submitted, events, outs

    tracer.enable()
    submitted, events, outs = sim.run_process(run(), until=sim.now + 900)
    tracer.disable()
    recs = tracer.drain()

    # the hook saw every token, in order
    rids = sorted({rid for rid, _, _ in seen})
    assert len(rids) == 4
    by_rid = {rid: [tok for r, tok, _ in seen if r == rid] for rid in rids}
    assert [by_rid[r] for r in rids] == [list(o) for o in outs]

    first = {rid: min(t for r, _, t in seen if r == rid) for rid in rids}
    starts = sorted(submitted.values())
    worst = 0.0
    for rid, t_sub in zip(rids, starts):
        ph = sorted((r for r in recs
                     if r.request_id == rid and r.name in REQUEST_PHASES),
                    key=lambda r: r.t0_ns)
        names = [r.name for r in ph]
        assert names[0] == "client.queue" and names[-1] == "client.sample"
        assert names.count("engine.prefill") == 2
        assert names.count("rpc.cpu_charge") == 2
        hops = [r.attrs["hop"] for r in ph if r.name == "rpc.open.send"]
        assert hops == [0, 1]
        assert all(a.t1_ns == b.t0_ns for a, b in zip(ph, ph[1:]))  # no gap
        assert all(r.t1_ns >= r.t0_ns for r in ph)                # no overlap
        total = sum(r.t1_ns - r.t0_ns for r in ph) * 1e-9
        ttft = first[rid] - t_sub
        worst = max(worst, abs(total - ttft) / ttft)
        charge = [r for r in ph if r.name == "rpc.cpu_charge"]
        assert all(r.attrs["virtual_s"] > 0 for r in charge)
    assert worst < 0.01, worst
    # with 2 slots for 4 requests, someone waited for a slot
    waits = [r.t1_ns - r.t0_ns for r in recs if r.name == "engine.admit_wait"]
    assert len(waits) == 8 and max(waits) > 0

    # decode rounds: every hop's step phases, and the counters in the spans
    steps = [r for r in recs if r.name == "engine.step"]
    ups = [r for r in recs if r.name == "kv.upload"]
    d = {k: sum(s.engine.stats[k] - b[k] for s, b in zip(servers, before))
         for k in ("steps", "step_sessions", "kv_bytes_uploaded",
                   "kv_bytes_live")}
    assert len(steps) == d["steps"] > 0
    assert sum(r.attrs["rows"] for r in steps) == d["step_sessions"]
    assert sum(r.attrs["bytes"] for r in ups) == d["kv_bytes_uploaded"]
    assert sum(r.attrs["live_bytes"] for r in ups) == d["kv_bytes_live"]
    assert 0 < d["kv_bytes_live"] < d["kv_bytes_uploaded"]
    ids = {r.span_id: r for r in recs}
    for name in ("kv.upload", "engine.fused", "kv.append"):
        kids = [r for r in recs if r.name == name]
        assert kids and all(ids[r.parent_id].name == "engine.step"
                            for r in kids)
    for name in ("rpc.step.send", "rpc.step.reply"):
        assert {r.attrs["hop"] if name == "rpc.step.send" else r.attrs["shard"]
                for r in recs if r.name == name} == {0, 1}
    assert {r.name for r in recs} >= {"request", "rpc.step.send",
                                      "rpc.step.reply"}
    assert all(r.attrs["tokens"] == 6 for r in recs if r.name == "request")


def test_tracing_leaves_the_simulation_unchanged(model, tracer):
    """A sanitized serving run's event-trace digest and outputs are the
    same with the tracer on and off."""
    cfg, _ = model
    ps = prompts(cfg, 3)
    digests, outs = [], []
    for on in (False, True):
        if on:
            tracer.enable()
        sim, _, client = serve(model, sim=Sim(seed=9, sanitize=True))
        out = generate(sim, client, ps, 4)
        sim.run(until=sim.now + 30)
        tracer.disable()
        digests.append(sim.trace_digest())
        outs.append([list(o) for o in out])
    assert digests[0] == digests[1]
    assert outs[0] == outs[1]
    assert any(r.name == "engine.fused" for r in tracer.drain())
