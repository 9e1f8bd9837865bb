"""simsan gates: end-to-end determinism / race / leak scenarios for CI.

Each gate builds a realistic workload on a ``Sim(sanitize=True)`` and
returns a :class:`GateRun` — the event-trace digest plus a *functional
fingerprint* (model output, converged store digest) and the sanitizer
findings.  :func:`run_gates` then enforces the contract:

* **determinism** — two runs of the same scenario under the same seed
  produce bit-identical event-trace digests;
* **schedule robustness** — perturbation runs (seeded tie-break shuffle
  of same-timestamp events) reproduce the same functional fingerprint
  even though the event order differs;
* **hygiene** — every run finishes with zero double-settles, zero
  orphaned (non-daemon) processes, and a leak audit at baseline.

Scenarios deliberately reuse the public builders (``make_fleet``,
``deploy_sharded``, the CRDT push plane) so the gate exercises the same
code paths the tests and examples do.  Each scenario runs a *warm-up*
request before snapshotting the leak baseline: connection pools and push
subscriptions are long-lived by design, so the audit only charges the
measured workload for resources it failed to return.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Generator, List, Optional

from ..core.fleet import make_fleet, wait_converged
from ..core.simnet import Sim

GateFn = Callable[[int, Optional[int]], "GateRun"]


@dataclass
class GateRun:
    """One execution of a gate scenario on a sanitizing Sim."""
    digest: str                      #: event-trace digest (order-sensitive)
    fingerprint: Any                 #: functional result (order-insensitive)
    double_settles: List[Dict[str, Any]]
    orphans: List[str]
    leaks: Dict[str, float]
    events: int

    @property
    def clean(self) -> bool:
        return not (self.double_settles or self.orphans or self.leaks)


@dataclass
class GateResult:
    name: str
    ok: bool
    failures: List[str] = field(default_factory=list)
    runs: List[GateRun] = field(default_factory=list)

    def format(self) -> str:
        head = f"gate {self.name}: {'ok' if self.ok else 'FAIL'}"
        if self.runs:
            head += (f" ({len(self.runs)} runs, "
                     f"{self.runs[0].events} events/run)")
        return "\n".join([head] + [f"  - {f}" for f in self.failures])


def _finish(sim: Sim, fingerprint: Any) -> GateRun:
    rep = sim.san_report()
    return GateRun(digest=rep["trace_digest"], fingerprint=fingerprint,
                   double_settles=rep["double_settles"],
                   orphans=rep["orphans"], leaks=rep["leaks"],
                   events=rep["events"])


# ---------------------------------------------------------------------------
# serving gate: sharded inference fleet, one generation round-trip
# ---------------------------------------------------------------------------


def serving_gate(seed: int = 0, perturb: Optional[int] = None) -> GateRun:
    """Deploy a 2-shard pipeline on a public fleet and drive one generate
    round-trip.  Fingerprint: the generated token ids."""
    import numpy as np

    from ..configs import get_config
    from ..models import ops_for
    from ..serving.sharded import ShardClient, deploy_sharded

    cfg = get_config("granite-8b").reduced(n_layers=2, d_model=32, vocab=128)
    ops = ops_for(cfg)
    import jax
    params = ops.init(cfg, jax.random.PRNGKey(seed))

    sim = Sim(seed=seed, sanitize=True, perturb=perturb)
    # public-only peers: no relay reservations, so the leak audit sees the
    # serving plane alone
    fleet = make_fleet(4, sim=sim, same_region="us", nat_kinds=[None] * 4)
    servers = deploy_sharded(fleet.peers[:2], cfg, params, "gate-svc")

    def announce() -> Generator:
        for s in servers:
            yield from s.announce()

    sim.run_process(announce(), until=sim.now + 600)
    client = ShardClient(fleet.peers[-1], cfg, "gate-svc", n_shards=2)
    toks = np.arange(8, dtype=np.int32)[None, :] % cfg.vocab

    def ask() -> Generator:
        out = yield client.submit(toks, 3)
        return out

    # warm-up dials every shard and populates the connection pool; only
    # then is the baseline meaningful for the audited request
    sim.run_process(ask(), until=sim.now + 900)
    sim.run(until=sim.now + 30)      # quiesce in-flight teardown first
    sim.leak_baseline()
    out = sim.run_process(ask(), until=sim.now + 900)
    sim.run(until=sim.now + 30)      # let in-flight teardown settle
    return _finish(sim, np.asarray(out).tolist())


# ---------------------------------------------------------------------------
# CRDT gate: replicated-store convergence over the push plane
# ---------------------------------------------------------------------------


def crdt_gate(seed: int = 0, perturb: Optional[int] = None) -> GateRun:
    """Fan a write out across a replicated fleet and wait for convergence.
    Fingerprint: (converged?, final store digest)."""
    sim = Sim(seed=seed, sanitize=True, perturb=perturb)
    fleet = make_fleet(5, sim=sim, same_region="us", nat_kinds=[None] * 5)
    writer = fleet.peers[0]
    # convergence rides the push plane (no periodic anti-entropy in the
    # fleet), so every replica must join the written namespaces' topics
    for n in fleet.peers:
        n.join_crdt_push("reg")
        n.join_crdt_push("gate")
    sim.run(until=sim.now + 5)       # pubsub subscription propagation

    def write_and_wait(tag: int) -> bool:
        for i in range(4):
            writer.store.orset(f"reg/gate{tag}").add(
                (tag, bytes([tag, i]) * 16), writer.host.name)
        writer.store.counter("gate/steps").increment(writer.host.name, tag)
        return wait_converged(sim, fleet.peers, timeout=300.0)

    write_and_wait(1)                # warm-up: push subscriptions + dials
    sim.run(until=sim.now + 30)      # quiesce in-flight teardown first
    sim.leak_baseline()
    ok = write_and_wait(2)
    sim.run(until=sim.now + 30)
    digest = writer.store.digest().hex()
    return _finish(sim, (ok, digest))


# ---------------------------------------------------------------------------
# fleet gate: scale-fleet churn — scored mesh + MST anti-entropy hygiene
# ---------------------------------------------------------------------------


def fleet_gate(seed: int = 0, perturb: Optional[int] = None) -> GateRun:
    """NAT-mixed scale fleet under a churn wave: a registry write rides the
    push plane while restarts tear mesh links down, then star-pattern MST
    anti-entropy repairs the restarted replicas.  Exercises graft/prune,
    subscription re-announce and the bounded mesh caches — the audit
    charges any mesh state (mcache, seen-set, pending IWANTs) a restart or
    repair fails to return.  Fingerprint: (converged?, store digest)."""
    from ..core.fleet import make_scale_fleet

    sim = Sim(seed=seed, sanitize=True, perturb=perturb)
    fleet = make_scale_fleet(48, sim=sim)
    writer = fleet.publics[0]
    hub = fleet.publics[1]
    for n in fleet.nodes:
        n.join_crdt_push("reg")
    sim.run(until=sim.now + 10)      # subscription propagation + mesh graft

    def sync_round() -> None:
        # every node anti-entropies with the hub concurrently; delta2 sync
        # is bidirectional, so one gather round + one distribute round
        # spreads the union even to replicas that missed every push
        for _ in range(2):
            procs = [sim.process(n.sync_crdt_with(hub.info()))
                     for n in fleet.nodes if n is not hub]
            deadline = sim.now + 60.0
            while sim.now < deadline and not all(p.triggered for p in procs):
                sim.run(until=min(deadline, sim.now + 0.5))

    def write_churn_converge(tag: int) -> bool:
        for i in range(4):
            writer.store.orset(f"reg/gate{tag}").add(
                (tag, bytes([tag, i]) * 16), writer.host.name)
        writer.store.counter("reg/steps").increment(writer.host.name, tag)
        sim.run(until=sim.now + 2)   # let the push wave land first
        fleet.churn_wave(0.05)       # restart NAT'd members mid-flight
        sim.run(until=sim.now + 5)   # restarted nodes re-announce + regraft
        sync_round()
        return wait_converged(sim, fleet.nodes, timeout=300.0)

    # warm-up: dials, push meshes, relay paths, first churn's re-wiring
    write_churn_converge(1)
    sim.run(until=sim.now + 30)      # heartbeats expire transient IWANTs
    sim.leak_baseline()
    ok = write_churn_converge(2)
    sim.run(until=sim.now + 30)
    digest = writer.store.digest().hex()
    return _finish(sim, (ok, digest))


GATES: Dict[str, GateFn] = {
    "serving": serving_gate,
    "crdt-sync": crdt_gate,
    "fleet": fleet_gate,
}


def run_gate(name: str, gate: GateFn, seed: int = 0,
             perturbations: int = 1) -> GateResult:
    """Double-run + perturbation-run one gate and check the contract."""
    failures: List[str] = []
    runs = [gate(seed, None), gate(seed, None)]
    if runs[0].digest != runs[1].digest:
        failures.append(
            f"non-deterministic: digests {runs[0].digest[:12]} != "
            f"{runs[1].digest[:12]} across identical runs")
    for p in range(perturbations):
        runs.append(gate(seed, p + 1))
        if runs[-1].fingerprint != runs[0].fingerprint:
            failures.append(
                f"perturbation {p + 1} changed the functional result — "
                "an outcome depends on same-timestamp event ordering")
    for i, r in enumerate(runs):
        label = f"run {i}" + (" (perturbed)" if i >= 2 else "")
        if r.double_settles:
            failures.append(f"{label}: {len(r.double_settles)} conflicting "
                            f"double-settle(s): {r.double_settles[0]}")
        if r.orphans:
            failures.append(f"{label}: orphaned processes: {r.orphans}")
        if r.leaks:
            failures.append(f"{label}: leak audit above baseline: {r.leaks}")
    return GateResult(name=name, ok=not failures, failures=failures,
                      runs=runs)


def run_all_gates(seed: int = 0, perturbations: int = 1,
                  names: Optional[List[str]] = None) -> List[GateResult]:
    selected = names if names is not None else list(GATES)
    out = []
    for name in selected:
        if name not in GATES:
            raise KeyError(f"unknown gate '{name}' (have: {list(GATES)})")
        out.append(run_gate(name, GATES[name], seed=seed,
                            perturbations=perturbations))
    return out
