"""Lattica quickstart: build a NAT-mixed mesh and use every subsystem.

    PYTHONPATH=src python examples/quickstart.py

Walks through the paper's four scenarios at toy scale:
  1. connectivity across NATs (AutoNAT -> relay -> DCUtR upgrade)
  2. content-addressed artifact publish + swarm fetch (decentralized CDN)
  3. delta-aware checkpoints: per-tensor DAGs, so a new version only moves
     the tensors that changed (hierarchical v2 manifests)
  4. CRDT replicated store convergence
  5. concurrent serving: continuous batching over a 2-shard × 2-replica
     fleet, with pressure-driven replica spawn on the hot shard
  5b. the decode hot path: fused paged-attention decode (one weight pass
     per batch) vs the per-slot loop, and int8_block wire quantization
     for checkpoint sync
  6. a typed RPC service (MethodSpec-declared unary + streaming methods,
     called through a generated stub)
  7. the analysis plane: latlint rules + sanitized simulation
  8. fleet scale: a 1k-node virtual-clock fleet (Trautwein NAT mix) under
     churn — scored-mesh push delivery, Merkle-summarized anti-entropy,
     summary bytes and mesh relay load on the dashboard
  9. collaborative training: one DiLoCo-style round across 8 workers in
     2 regions joined by a thin link — H local steps, then a top-k +
     int8 compressed pseudo-gradient exchange coordinated entirely
     through the CRDT store (no coordinator), bytes-on-wire printed
"""

import sys

sys.path.insert(0, "src")

from repro.core import Service, streaming, unary
from repro.core.fleet import make_fleet
from repro.core.service import Fixed, pickled


def main():
    print("== building a 10-peer mesh behind mixed NATs ==")
    fleet = make_fleet(10, seed=7)
    sim = fleet.sim
    for n in fleet.peers[:5]:
        print(f"  {n.host.name}: nat={type(n.host.nat).__name__ if n.host.nat else 'public'}"
              f" reachability={n.transport.reachability}")

    a, b = fleet.peers[0], fleet.peers[5]

    # -- 1. connectivity ----------------------------------------------------
    def connect():
        conn = yield from a.connect_info(b.info())
        rtt = yield from a.transport.ping(conn)
        return conn, rtt

    conn, rtt = sim.run_process(connect())
    print(f"\n== 1. {a.host.name} -> {b.host.name}: "
          f"{'RELAYED' if conn.relayed else 'DIRECT'} path, rtt={rtt*1000:.1f}ms ==")

    # -- 1b. predicted-port punching through a symmetric NAT ----------------
    # A symmetric NAT mints a fresh external port per destination, so the
    # address it advertises is never the one it will use toward the peer —
    # naive hole punching always fails.  DCUtR v2 fingerprints the box's
    # port allocator by probing the relay from fresh sockets; against a
    # sequential (or fixed-delta) allocator the other side sprays the
    # predicted window base+stride*k and catches the fresh mapping.
    from repro.core import NATKind
    from repro.core.fleet import make_fleet as _mk

    sfleet = _mk(2, seed=11, nat_kinds=[
        (NATKind.SYMMETRIC, "sequential", 1),   # predictable allocator
        NATKind.PORT_RESTRICTED,                # strictest cone filter
    ])
    sym, cone = sfleet.peers

    def punch():
        c = yield from cone.connect_info(sym.info())
        return c

    sconn = sfleet.sim.run_process(punch())
    print(f"== 1b. symmetric(sequential) <- port_restricted: "
          f"{'RELAYED' if sconn.relayed else 'DIRECT (predicted-port punch)'}; "
          f"fingerprint probes={sym.transport.stats['fingerprint_probes']}, "
          f"predicted punches="
          f"{sym.transport.stats['predicted_punch_ok'] + cone.transport.stats['predicted_punch_ok']} ==")

    # -- 2. content distribution --------------------------------------------
    blob = bytes(range(256)) * 4096            # 1 MiB artifact

    def publish_fetch():
        root = yield from a.publish_artifact(blob, announce_topic="demo")
        t0 = sim.now
        got = yield from b.fetch_artifact(root)
        return root, got == blob, sim.now - t0

    root, ok, dt = sim.run_process(publish_fetch())
    print(f"== 2. published {len(blob)//1024} KiB as {root}; "
          f"fetched ok={ok} in {dt:.2f}s (sim) ==")

    # -- 3. delta-aware checkpoints -------------------------------------------
    # Each tensor becomes its own sub-DAG under a hierarchical manifest, so
    # version 2 reuses the unchanged tensors' CIDs verbatim: fetchers only
    # swarm the changed sub-DAGs, and publishers report the reuse fraction.
    import pickle

    import numpy as np

    from repro.checkpoint.lattica_ckpt import (fetch_checkpoint,
                                               publish_checkpoint)
    from repro.core.cid import decode_manifest_v2

    rng = np.random.default_rng(0)
    params_v1 = {f"layer{i}/w": rng.integers(0, 256, 96 * 1024, dtype=np.uint8)
                 for i in range(8)}
    params_v2 = dict(params_v1)
    params_v2["layer3/w"] = rng.integers(0, 256, 96 * 1024, dtype=np.uint8)

    def sync_versions():
        r1 = yield from publish_checkpoint(a, params_v1, 1, "quickstart")
        yield from fetch_checkpoint(b, r1, like=params_v1, fleet="quickstart")
        base_bytes = b.bitswap.stats["bytes_fetched"]
        r2 = yield from publish_checkpoint(a, params_v2, 2, "quickstart",
                                           base=r1)
        yield from fetch_checkpoint(b, r2, like=params_v1, fleet="quickstart")
        # latlint: disable=L003 locally-published manifest, not peer bytes
        meta = pickle.loads(decode_manifest_v2(a.blockstore.peek(r2))[2])
        return meta["delta"], b.bitswap.stats["bytes_fetched"] - base_bytes

    delta, v2_bytes = sim.run_process(sync_versions())
    print(f"== 3. checkpoint v2 (1 of 8 tensors changed): publisher reused "
          f"{delta['reused_bytes']//1024} KiB, new {delta['new_bytes']//1024} "
          f"KiB; fetcher moved only {v2_bytes//1024} KiB ==")

    # -- 3b. content-defined chunking ----------------------------------------
    # Fixed-size chunks lose all sharing the moment bytes *shift*: grow a
    # vocabulary and every downstream chunk gets a fresh CID.  A `cdc`
    # ChunkSpec places boundaries with a rolling hash, so they re-synchronize
    # right after the edit and the unchanged tail keeps its leaf CIDs.  The
    # spec is recorded in the manifest meta; publishing with base=<previous>
    # reuses it, so boundaries reproduce across versions.
    from repro.core.cid import ChunkSpec

    grown = {"vocab/w": np.concatenate(
        [rng.integers(0, 256, 2048, dtype=np.uint8), params_v1["layer0/w"]])}

    def shifted_edit(spec):
        r1 = yield from publish_checkpoint(
            a, {"vocab/w": params_v1["layer0/w"]}, 1, f"cdc-{spec.strategy}",
            spec=spec)
        r2 = yield from publish_checkpoint(
            a, grown, 2, f"cdc-{spec.strategy}", base=r1)
        # latlint: disable=L003 locally-published manifest, not peer bytes
        return pickle.loads(decode_manifest_v2(
            a.blockstore.peek(r2))[2])["delta"]

    for spec in (ChunkSpec(strategy="fixed", chunk_size=16 * 1024),
                 ChunkSpec.cdc(avg_size=16 * 1024)):
        d = sim.run_process(shifted_edit(spec))
        total = d["new_bytes"] + d["reused_bytes"]
        print(f"== 3b. {spec.strategy:>5} chunks, 2 KiB prepended to a 96 KiB "
              f"tensor: re-publish reuses {d['reused_bytes']/total:.0%} of "
              f"bytes ==")

    # -- 4. CRDT store: watch + delta push ------------------------------------
    # The replicated store is a *delta-state* CRDT document: every local
    # mutation ships as a minimal per-key delta on a crdt/<ns> pubsub
    # topic (canonical JSON, not pickle), so a subscriber's watch callback
    # fires one gossip round after a remote write — no anti-entropy tick,
    # no full-state swap.
    events = []
    b.watch_crdt("train/", lambda key, value, origin:
                 events.append((key, value, origin)))
    sim.run(until=sim.now + 2)       # subscription update reaches the mesh

    pushed0 = a.crdt_stats["push_bytes"]
    a.store.counter("train/steps").increment(a.host.name, 42)
    a.store.orset("train/ckpts").add("v1", a.host.name)
    sim.run(until=sim.now + 3)       # one gossip round
    print(f"== 4. CRDT delta push: {b.host.name} watch fired {events}; "
          f"subscriber sees steps="
          f"{b.store.counter('train/steps').value()}, "
          f"ckpts={b.store.orset('train/ckpts').value()}; "
          f"{a.crdt_stats['push_bytes'] - pushed0} B on the wire vs "
          f"{len(a.store.serialize())} B full state ==")

    # anti-entropy is the mop-up path, and it too moves per-key deltas
    # now: digest probe -> per-key digest summary -> delta transfer
    b.store.orset("train/ckpts").add("v2", b.host.name)

    def sync():
        yield from a.sync_crdt_with(b.info())

    sim.run_process(sync())
    print(f"== 4b. delta anti-entropy: ckpts={a.store.orset('train/ckpts').value()}, "
          f"rounds={a.crdt_stats['delta_exchanges']} delta / "
          f"{a.crdt_stats['full_exchanges']} full, "
          f"{a.crdt_stats['tx_bytes'] + a.crdt_stats['rx_bytes']} B total ==")

    # -- 5. concurrent serving: continuous batching + pressure replicas ------
    # Shard servers batch every live decode session into each RPC step
    # (paged KV slots, FIFO admission) and publish slot occupancy/queue
    # depth into the CRDT plane; an idle peer that observes sustained
    # hot-shard pressure fetches the shard's param sub-DAG off the
    # content plane and registers as a fresh DHT provider.
    import jax

    from repro.configs import get_config
    from repro.models import ops_for
    from repro.serving import PressureMonitor, ShardClient, serve_fleet

    scfg = get_config("granite-8b").reduced(n_layers=4, d_model=64, vocab=256)
    sparams = ops_for(scfg).init(scfg, jax.random.PRNGKey(0))
    sv_fleet = make_fleet(8, seed=23, same_region="us")
    ssim = sv_fleet.sim
    servers = ssim.run_process(
        serve_fleet(sv_fleet.peers[:4], scfg, sparams, "demo", replicas=2,
                    n_slots=2),
        until=ssim.now + 900)
    client = ShardClient(sv_fleet.peers[-1], scfg, "demo", n_shards=2)
    mon = PressureMonitor(sv_fleet.peers[5], scfg, "demo", hot_occupancy=0.5,
                          sustain=2, interval=0.3, n_slots=2)
    ssim.process(mon.run())
    prompts = [np.asarray(
        jax.random.randint(jax.random.PRNGKey(50 + i), (1, 8), 0, scfg.vocab),
        np.int32) for i in range(4)]

    def serve_demo():
        t0 = ssim.now
        reqs = [dict(tokens=prompts[i % len(prompts)], n_tokens=12)
                for i in range(12)]
        outs = yield from client.generate_concurrent(reqs)
        return outs, ssim.now - t0

    outs, sdt = ssim.run_process(serve_demo(), until=ssim.now + 3600)
    ssim.run(until=ssim.now + 30)          # let a pending spawn finish
    mon.stop()
    done = sum(1 for o in outs if o is not None)
    steps = sum(s.engine.stats["steps"] for s in servers)
    sess = sum(s.engine.stats["step_sessions"] for s in servers)
    print(f"== 5. serving: {done}/12 concurrent clients completed, "
          f"{done * 12 / sdt:.0f} tok/s, "
          f"{sess / max(1, steps):.1f} sessions/batched step; "
          f"pressure spawned {mon.stats['spawned']} replica(s) on "
          f"{sv_fleet.peers[5].host.name} ==")

    # -- 5b. fast decode + quantized hot paths --------------------------------
    # Each decode step above is ONE fused paged-attention pass over every
    # live session: the weights are read once per batch, and each slot's
    # KV pages are gathered from a shared pool (the Pallas kernel in
    # kernels/paged_attention.py; CPU runs the jnp formulation).  The
    # per-slot fallback pays a full weight read per session per token —
    # at decode, which is bandwidth-bound, that is the whole difference
    # (measured 6.4x tokens/s at 8 sessions: BENCH_decode_step.json).
    from repro.core.simnet import Sim as _Sim
    from repro.serving.batch import BatchEngine
    from repro.serving.sharded import ShardModule

    perf = {}
    for label, kw in (("fused", {}), ("unfused", {"fused": False})):
        dsim = _Sim(seed=5)
        eng = BatchEngine(
            ShardModule(scfg, sparams, (0, scfg.n_layers), is_first=True,
                        is_last=True), dsim, n_slots=4, page_size=8, **kw)
        toks = {}
        for i in range(4):
            out, _ = dsim.run_process(eng.open(f"s{i}", prompts[i], 64))
            toks[f"s{i}"] = int(np.argmax(out[0]))
        cost, n_tok = 0.0, 0
        for _ in range(16):
            out, served, c = eng.step(
                list(toks), np.asarray([toks[s] for s in toks], np.int32))
            for sid, row in zip(served, out):
                toks[sid] = int(np.argmax(row))
            cost += c
            n_tok += len(served)
        perf[label] = n_tok / cost
    print(f"== 5b. decode: fused {perf['fused']:.0f} tok/s vs per-slot "
          f"{perf['unfused']:.0f} "
          f"({perf['fused'] / perf['unfused']:.1f}x) ==")

    # Checkpoint sync can quantize the *wire* the same way: int8 per
    # 4096-element block with f32 scale+zero-point, per-tensor parts, the
    # fp32 master staying lossless on the publisher.  Composed with the
    # delta plane (only churned tensors move at all), a 10%-churn sync
    # round moves ~0.25x the fp32 bytes (BENCH_model_sync.json).
    from repro.checkpoint import params_to_parts

    fp_bytes = sum(len(r) for _, r, _ in params_to_parts(sparams))
    q_bytes = sum(len(r) for _, r, _ in
                  params_to_parts(sparams, quant="int8_block"))
    print(f"== 5c. wire quantization: int8_block parts are "
          f"{q_bytes / fp_bytes:.2f}x the fp32 encoding "
          f"({fp_bytes // 1024} KiB -> {q_bytes // 1024} KiB), "
          f"error <= block_range/508 per element ==")

    # -- 6. typed RPC service -------------------------------------------------
    # Declare methods with MethodSpecs: wire name, codecs (which compute the
    # simulated wire size from the payload), idempotency and deadline.  The
    # handler returns just the response — no hand-passed size constants.
    class DemoService(Service):
        name = "demo"

        @unary("demo.double", request=Fixed(64), response=pickled(floor=64),
               idempotent=True, timeout=5.0)
        def double(self, payload, ctx):
            yield ctx.cpu(1e-6)
            return payload * 2

        @streaming("demo.squares")
        def squares(self, chan, ctx):
            for i in range(5):
                yield from chan.send(i * i, 64)
            chan.end()

    b.serve(DemoService())
    stub = a.stub(DemoService, b.info())   # reuses the existing connection

    def rpc():
        x = yield from stub.double(21)     # deadline + idempotent retry built in
        chan = yield from stub.squares()   # opens a backpressured channel
        got = []
        try:
            while True:
                got.append((yield from chan.recv(timeout=5.0)))
        except Exception:
            pass
        return x, got

    x, squares = sim.run_process(rpc())
    print(f"== 6. unary double(21)={x}; streamed squares={squares} ==")

    # -- fleet dashboard -------------------------------------------------------
    from repro.core.metrics import dashboard
    print("\n== fleet dashboard ==")
    print(dashboard(fleet.all_nodes))

    # -- 7. analysis plane -----------------------------------------------------
    # The repo lints itself: `python -m repro.analysis --strict` runs the
    # latlint rules (L001 no wall-clock/global-random in sim code, L002 no
    # raw RPC plane, L003 no unsafe pickle, L004 hedging only over
    # idempotent MethodSpecs, L005 generator-process hygiene, L006 Pallas
    # BlockSpec/grid/VMEM sanity); deliberate exceptions carry inline
    # `# latlint: disable=L00x <reason>` waivers.  Sanitized simulation is
    # one constructor flag away:
    from repro.analysis import run_lint
    report = run_lint([__file__])
    print(f"\n== 7. latlint on this example: "
          f"{'clean' if not report.active else report.format_text()} ==")

    from repro.core.simnet import Sim
    ssim = Sim(seed=7, sanitize=True)     # records an event-trace digest,
    ssim.run(until=1.0)                   # double-settles, orphans, leaks
    print(f"simsan digest (empty run): {ssim.trace_digest()[:16]}…  "
          "(CI double-runs serving/CRDT scenarios and diffs these)")

    # -- 8. fleet scale: 1k virtual-clock nodes under churn -------------------
    # make_scale_fleet skips per-node bootstrap: reachability comes from
    # the Trautwein et al. measured NAT mix, overlay edges are pre-wired,
    # so 1000 nodes stand up in about a second of wall time and churn
    # scenarios run entirely on the virtual clock.  A registry write
    # rides the scored gossipsub mesh to every subscriber; restarted
    # members catch up through Merkle-summarized anti-entropy (O(log n)
    # probe bytes instead of the flat per-key summary).
    import time

    from repro.core.fleet import make_scale_fleet

    t0 = time.time()    # latlint: disable=L001 host-side build timing
    kfleet = make_scale_fleet(1000, seed=3)
    ksim = kfleet.sim
    for n in kfleet.nodes:
        n.join_crdt_push("reg")
    ksim.run(until=ksim.now + 10)         # subscriptions + mesh formation
    writer = kfleet.publics[0]
    for i in range(4):
        writer.store.orset("reg/members").add(f"m{i}", writer.host.name)
    ksim.run(until=ksim.now + 6)          # ~3 gossip rounds
    reached = sum(1 for n in kfleet.nodes
                  if n.store.orset("reg/members").value())
    victims = kfleet.churn_wave(0.01)     # restart 1% of the NAT'd nodes
    hub = kfleet.publics[1]
    # a registry shard only the hub holds (its namespace has no push
    # subscribers): the restarted nodes pick it up via anti-entropy —
    # digest probe, then a Merkle summary-forest walk that localizes the
    # divergence in O(log n) probe bytes instead of a flat O(keys) round
    for i in range(64):
        hub.store.register(f"mreg/shard{i}").set(i, ksim.now, hub.host.name)

    def mop_up():
        for v in victims:
            yield from v.sync_crdt_with(hub.info())

    ksim.run_process(mop_up(), until=ksim.now + 120)
    probe = sum(n.crdt_stats["mst_probe_bytes"] for n in kfleet.nodes)
    probes = sum(n.crdt_stats["mst_exchanges"] for n in kfleet.nodes)
    print(f"\n== 8. fleet scale: 1000 nodes built+converged in "
          f"{time.time() - t0:.1f}s wall, "   # latlint: disable=L001 banner
          f"{ksim.now:.0f}s virtual; push reached {reached}/1000 nodes; "
          f"churned {len(victims)} nodes, anti-entropy mopped up with "
          f"{probe // max(1, probes)} B/probe ==")
    # the dashboard aggregates the new fleet gauges: mesh relay load
    # (max vs mean pubsub.forwarded — a healthy scored mesh keeps them
    # close) and summary_bytes (Merkle probe traffic); full per-node rows
    # are printed for a small sample only
    print("== 8b. dashboard (4-node sample of the 1k fleet) ==")
    print(dashboard([writer, hub] + victims[:2]))

    # -- 9. collaborative training round across 2 regions --------------------
    # DiLoCo-style: every worker runs H local AdamW steps, publishes its
    # pseudo-gradient top-k sparsified + int8-quantized as a content DAG,
    # and the round closes through CRDT quorum — no coordinator anywhere.
    # The regions= / bandwidth= knobs model two datacenters joined by a
    # thin transcontinental path; the compressed exchange is what makes
    # that link survivable.
    import jax

    from repro.configs import get_config
    from repro.data import make_batch_iterator
    from repro.optim import cosine_schedule
    from repro.train import train_state_init
    from repro.train.collab import CollabConfig, CollabWorker

    tcfg = get_config("minicpm-2b").reduced(n_layers=2, d_model=64, vocab=128)
    tfleet = make_scale_fleet(
        16, seed=21, nat_mix=[(None, 1.0)], regions=["us", "eu"],
        latency={"inter": 60e-3}, bandwidth={"inter": 1.2e7})
    tsim = tfleet.sim
    sched = cosine_schedule(1e-3, 5, 100)
    workers = []
    for i in range(8):
        data = make_batch_iterator(tcfg.vocab, 32, global_batch=8,
                                   n_shards=8, shard=i, seed=1)
        workers.append(CollabWorker(
            tfleet.nodes[i], tcfg,
            train_state_init(tcfg, jax.random.PRNGKey(0)), sched, data,
            "quickstart", collab=CollabConfig(inner_steps=6, settle=0.5),
            step_seconds=0.2))
    tprocs = [tsim.process(w.run(1)) for w in workers]
    tsim.run(until=tsim.now + 300)
    assert all(p.triggered and not p.failed for p in tprocs)
    wire = sum(w.stats["wire_bytes"] for w in workers)
    dense = sum(w.stats["dense_bytes"] for w in workers)
    digests = {w.outer_digest() for w in workers}
    regions = sorted({w.node.host.region for w in workers})
    print(f"\n== 9. collaborative round: 8 workers across {regions}, "
          f"H=6 inner steps ==")
    print(f"pseudo-gradient on the wire: {wire/1024:.0f} KiB compressed "
          f"vs {dense/1024:.0f} KiB naive fp32 ({wire/dense:.3f}x); "
          f"outer digests identical on all 8: {len(digests) == 1}")

    print(f"\nsim clock: {sim.now:.2f}s — done.")


if __name__ == "__main__":
    main()
