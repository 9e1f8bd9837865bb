"""latlint rule fixtures (each L001–L006 firing exactly once), waiver
parsing, and the simsan sanitizer: determinism digests, perturbation,
double-settle/orphan detection, leak audits, and regressions for the
stream-hygiene fixes."""

import textwrap

import pytest

from repro.analysis import run_lint
from repro.core import LatticaNode, Network, Sim, call_unary
from repro.core.fleet import make_fleet
from repro.core.nat import NATKind
from repro.serving.batch import KVPool

# ---------------------------------------------------------------------------
# latlint fixtures — one rule, one violation
# ---------------------------------------------------------------------------


def lint_src(tmp_path, src, name="fixture.py"):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(src))
    return run_lint([f])


def only_active(report, rule):
    assert [v.rule for v in report.active] == [rule], report.format_text()
    return report.active[0]


def test_l001_wall_clock_fires_once(tmp_path):
    rep = lint_src(tmp_path, """\
        import time

        def handler(payload):
            return {"at": time.time(), "payload": payload}
        """)
    v = only_active(rep, "L001")
    assert "time.time()" in v.message


def test_l001_from_import_and_global_random(tmp_path):
    rep = lint_src(tmp_path, """\
        from time import monotonic as mono
        import random

        def jitter():
            return mono() + random.random()
        """)
    assert sorted(v.rule for v in rep.active) == ["L001", "L001"]


def test_l001_sim_rng_is_fine(tmp_path):
    rep = lint_src(tmp_path, """\
        def jitter(sim):
            return sim.now + sim.rng.random()
        """)
    assert rep.active == []


@pytest.mark.parametrize("name,fires", [
    ("repro/core/tracing.py", False),
    ("repro/core/tracing_util.py", True),
    ("repro/serving/batch.py", True),
    ("repro/core/service.py", True),
])
def test_l001_exempts_only_the_tracer(tmp_path, name, fires):
    rep = lint_src(tmp_path, """\
        import time

        def stamp():
            return time.perf_counter_ns()
        """, name=name)
    assert [v.rule for v in rep.active] == (["L001"] if fires else [])


def test_l002_raw_rpc_fires_once(tmp_path):
    rep = lint_src(tmp_path, """\
        def wire(node, handler):
            node.router.register_unary("x.op", handler)
        """)
    v = only_active(rep, "L002")
    assert "typed service plane" in v.message


def test_l002_exempt_in_service_module(tmp_path):
    rep = lint_src(tmp_path, """\
        def wire(node, handler):
            node.router.register_unary("x.op", handler)
        """, name="repro/core/service.py")
    assert rep.active == []


def test_l003_pickle_fires_once(tmp_path):
    rep = lint_src(tmp_path, """\
        import pickle

        def decode(blob):
            return pickle.loads(blob)
        """)
    v = only_active(rep, "L003")
    assert "safepickle" in v.message


def test_l004_hedging_non_idempotent_fires_once(tmp_path):
    rep = lint_src(tmp_path, """\
        from repro.core.service import unary

        class Svc:
            @unary("infer", timeout=30.0)
            def infer(self, payload, ctx):
                yield 0
                return payload

        def caller(sim, stub, payload):
            def attempt():
                resp = yield from stub.infer(payload)
                return resp
            return hedged_call(sim, [attempt, attempt])
        """)
    v = only_active(rep, "L004")
    assert "'infer'" in v.message


def test_l004_declared_idempotent_is_fine(tmp_path):
    rep = lint_src(tmp_path, """\
        from repro.core.service import unary

        class Svc:
            @unary("score", timeout=30.0, idempotent=True)
            def score(self, payload, ctx):
                yield 0
                return payload

        def caller(sim, stub, payload):
            def attempt():
                resp = yield from stub.score(payload)
                return resp
            return hedged_call(sim, [attempt, attempt])
        """)
    assert rep.active == []


def test_l005_bare_generator_call_fires_once(tmp_path):
    rep = lint_src(tmp_path, """\
        def pump(chan):
            while True:
                yield chan.recv()

        def serve(chan):
            pump(chan)
            return True
        """)
    v = only_active(rep, "L005")
    assert "pump" in v.message


def test_l005_ambiguous_name_is_skipped(tmp_path):
    rep = lint_src(tmp_path, """\
        def send(x):
            yield x

        class Plain:
            def send(self, x):
                return x

        def use(obj, x):
            obj.send(x)
        """)
    assert rep.active == []


def test_l007_flat_summary_fires_once(tmp_path):
    rep = lint_src(tmp_path, """\
        def probe(store, stub):
            summary = encode_summary(store.key_digests())
            yield from stub.summary(summary)
        """)
    v = only_active(rep, "L007")
    assert "O(keys)" in v.message and "summary_forest" in v.message


def test_l007_waiver_and_crdt_module_exempt(tmp_path):
    waived = lint_src(tmp_path, """\
        def probe(store):
            # latlint: disable=L007 serves the flat-v2 wire for old peers
            return store.key_digests()
        """)
    assert waived.active == [] and [v.rule for v in waived.waived] == ["L007"]
    defining = lint_src(tmp_path, """\
        def summary_of(store):
            return store.key_digests()
        """, name="repro/core/crdt.py")
    assert defining.active == []


def test_l007_mst_walk_is_fine(tmp_path):
    rep = lint_src(tmp_path, """\
        def probe(store):
            forest = store.summary_forest()
            return store.summary_roots(), forest
        """)
    assert rep.active == []


def test_l006_vmem_budget_fires_once(tmp_path):
    rep = lint_src(tmp_path, """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((2048, 4096), lambda i: (0, i))],
                out_specs=pl.BlockSpec((2048, 4096), lambda i: (0, i)),
            )(x)
        """)
    v = only_active(rep, "L006")
    assert "VMEM" in v.message


def test_l006_index_map_arity_and_rank(tmp_path):
    rep = lint_src(tmp_path, """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                kernel,
                grid=(4, 8),
                in_specs=[pl.BlockSpec((8, 16), lambda i: (0, i))],
                out_specs=pl.BlockSpec((8, 16), lambda i, j: (i, j)),
            )(x)
        """)
    assert [v.rule for v in rep.active] == ["L006"]
    assert "2 grid dims" in rep.active[0].message


def test_l006_grid_divisibility_guard(tmp_path):
    bad = """\
        import jax.experimental.pallas as pl

        def launch(x, S, bq=128):
            {guard}
            return pl.pallas_call(
                lambda x_ref, o_ref: None,
                grid=(S // bq,),
                in_specs=[pl.BlockSpec((8, 16), lambda i: (0, i))],
                out_specs=pl.BlockSpec((8, 16), lambda i: (0, i)),
            )(x)
        """
    rep = lint_src(tmp_path, bad.format(guard="pass"))
    assert [v.rule for v in rep.active] == ["L006"]
    assert "assert S % bq == 0" in rep.active[0].message
    rep = lint_src(tmp_path, bad.format(guard="assert S % bq == 0"))
    assert rep.active == []


# ---------------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------------


def test_waiver_trailing_with_reason(tmp_path):
    rep = lint_src(tmp_path, """\
        import time

        def banner():
            return time.time()  # latlint: disable=L001 CLI banner timing
        """)
    assert rep.active == []
    assert len(rep.waived) == 1
    assert rep.waived[0].waive_reason == "CLI banner timing"


def test_waiver_without_reason_does_not_waive(tmp_path):
    rep = lint_src(tmp_path, """\
        import time

        def banner():
            return time.time()  # latlint: disable=L001
        """)
    v = only_active(rep, "L001")
    assert "missing a reason" in v.message


def test_waiver_line_above_and_file_level(tmp_path):
    rep = lint_src(tmp_path, """\
        import time

        def banner():
            # latlint: disable=L001 standalone waiver above the call
            return time.time()
        """)
    assert rep.active == [] and len(rep.waived) == 1
    rep = lint_src(tmp_path, """\
        # latlint: disable-file=L001 whole module is host-side CLI code
        import time

        def a():
            return time.time()

        def b():
            return time.time()
        """)
    assert rep.active == [] and len(rep.waived) == 2


# ---------------------------------------------------------------------------
# simsan: determinism digests + perturbation
# ---------------------------------------------------------------------------


def _digest_scenario(seed, perturb=None):
    import random as stdlib_random
    sim = Sim(seed=seed, sanitize=True, perturb=perturb)
    order = []

    def worker(name):
        # per-worker seeded delays: drawing from the shared sim.rng here
        # would make the delays depend on same-time scheduling order (the
        # exact order-dependence the perturbation mode exists to surface)
        rng = stdlib_random.Random(f"{seed}:{name}")
        for _ in range(3):
            yield sim.timeout(rng.random())
            order.append((name, sim.now))

    for name in "abcd":
        sim.process(worker(name))
    sim.run()
    return sim.trace_digest(), order


def test_trace_digest_double_run_identical():
    d1, o1 = _digest_scenario(seed=11)
    d2, o2 = _digest_scenario(seed=11)
    assert d1 == d2 and o1 == o2


def test_trace_digest_differs_across_seeds():
    d1, _ = _digest_scenario(seed=11)
    d2, _ = _digest_scenario(seed=12)
    assert d1 != d2


def test_perturbation_keeps_functional_result():
    _, base = _digest_scenario(seed=11)
    for p in (1, 2, 3):
        _, got = _digest_scenario(seed=11, perturb=p)
        # distinct event times: dispatch order — and thus the functional
        # result — must be independent of the tie-break key
        assert got == base


def test_perturbation_reorders_simultaneous_events():
    def ties(perturb=None):
        sim = Sim(seed=0, sanitize=True, perturb=perturb)
        order = []

        def worker(name):
            for _ in range(3):
                yield sim.timeout(1.0)     # every worker wakes at t=1,2,3
                order.append(name)

        for name in "abcdef":
            sim.process(worker(name))
        sim.run()
        return order

    base = ties()
    assert base[:6] == list("abcdef")      # FIFO tie-break without perturb
    assert any(ties(perturb=p) != base for p in (1, 2, 3))


def test_trace_digest_requires_sanitize():
    sim = Sim(seed=0)
    with pytest.raises(Exception):
        sim.trace_digest()


# ---------------------------------------------------------------------------
# simsan: double-settle + orphan detection
# ---------------------------------------------------------------------------


def test_double_settle_benign_and_conflicting():
    sim = Sim(seed=0, sanitize=True)
    evt = sim.event()
    evt.succeed(5)
    evt.succeed(5)                         # idempotent re-settle: benign
    assert sim.san_report()["double_settles"] == []
    evt.succeed(6)                         # same kind, different value
    evt.fail(RuntimeError("late loser"))   # conflicting kind
    settles = sim.san_report()["double_settles"]
    assert len(settles) == 2
    assert settles[0]["first"] == "succeed" and settles[0]["second"] == "succeed"
    assert settles[1]["second"] == "fail"


def test_orphaned_process_reported_daemon_exempt():
    sim = Sim(seed=0, sanitize=True)

    def stuck():
        yield sim.event()

    def service_loop():
        while True:
            yield sim.timeout(1.0)

    def finishes():
        yield sim.timeout(0.5)

    sim.process(stuck())
    sim.process(service_loop(), daemon=True)
    sim.process(finishes())
    sim.run(until=10.0)
    orphans = sim.san_report()["orphans"]
    assert len(orphans) == 1 and "stuck" in orphans[0]


# ---------------------------------------------------------------------------
# simsan: leak audit
# ---------------------------------------------------------------------------


def _pair(seed=0, sanitize=True):
    sim = Sim(seed=seed, sanitize=sanitize)
    net = Network(sim)
    a = LatticaNode(net, "a", region="us", zone="a")
    b = LatticaNode(net, "b", region="us", zone="a")

    def conn():
        c = yield from a.connect_info(b.info())
        return c

    return sim, a, b, sim.run_process(conn())


def test_leak_fixture_half_open_stream_and_kv_page():
    sim, a, b, conn = _pair()
    pool = KVPool(n_layers=2, n_kv_heads=2, head_dim=16, page_size=8)
    sim.register_leak_check("kv.pages:test", pool.pages_in_use)
    sim.run(until=sim.now + 5)
    sim.leak_baseline()

    # leak 1: initiator opens a stream and walks away without closing it
    stream = conn.open_stream("fixture.unhandled", a.host)
    sim.run(until=sim.now + 5)
    # leak 2: KV pages allocated for a session and never freed
    pages = pool.alloc(3)

    audit = sim.leak_audit()
    assert audit["net.half_open_streams"] == 1
    assert audit["kv.pages:test"] == 3

    stream.close()
    pool.free(pages)
    assert sim.leak_audit() == {}


def test_unary_rpc_leaves_no_half_open_streams():
    sim, a, b, conn = _pair()

    def echo(payload, ctx):
        yield ctx.cpu(1e-6)
        return ("echo", payload), 64

    b.router.register_unary("t.echo", echo)
    sim.run(until=sim.now + 5)
    sim.leak_baseline()

    def run():
        for i in range(3):
            yield from call_unary(a.host, conn, "t.echo", {"i": i})

    sim.run_process(run(), until=sim.now + 60)
    sim.run(until=sim.now + 10)
    assert sim.leak_audit() == {}


def test_traversal_protocols_return_streams_to_baseline():
    """Regression for the handler-side stream hygiene fixes: a full
    NAT-traversal connect (AutoNAT, relay, DCUtR/ping as needed) must not
    strand stream endpoints or relay reservations."""
    sim = Sim(seed=7, sanitize=True)
    fleet = make_fleet(
        3, sim=sim, same_region="us",
        nat_kinds=[NATKind.PORT_RESTRICTED, NATKind.PORT_RESTRICTED, None])
    sim.run(until=sim.now + 30)
    sim.leak_baseline()

    conn = sim.run_process(
        fleet.peers[0].connect_info(fleet.peers[1].info()),
        until=sim.now + 300)
    assert conn is not None
    sim.run(until=sim.now + 30)
    audit = sim.leak_audit()
    assert "net.half_open_streams" not in audit, audit
    assert not any(k.startswith("relay.reservations") for k in audit), audit
    assert sim.san_report()["double_settles"] == []
