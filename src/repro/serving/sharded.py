"""Sharded inference over the Lattica mesh (paper Fig. 1, Scenario 4).

A model is split into pipeline shards; each shard runs on a peer (possibly
behind a NAT) and serves the ``infer.<fleet>`` RPC.  Shard servers announce
themselves as DHT providers of ``shard/<fleet>/<i>``; the shard-aware client
stub resolves providers per hop, streams activations through the pipeline,
and **transparently fails over** to replica shards via a fresh DHT lookup
when a provider dies — the availability story of the paper's §2 RPC layer.

Each shard serves one RPC surface, ``infer.v2.*.<fleet>.<i>``, the
continuous-batching plane: ``open`` admits a session into the shard's
:class:`~repro.serving.batch.BatchEngine` slot table (FIFO-queueing when
full), ``step`` advances *many* sessions in one wire message, ``close``
evicts.  One RPC per shard hop per decode iteration is shared by every
active session, which is where batching beats one request at a time:
per-message CPU and link latency amortize across the batch while
per-token FLOPs stay identical.

:class:`ShardClient` routes via a load-aware :class:`LoadAwareRouter`
(EWMA latency / error rate / in-flight depth per provider) instead of
first-successful-dial, and **migrates** sessions
mid-generation: when a provider dies between decode steps the driver
replays prompt ⊕ generated-so-far through a freshly routed chain, so a
crash loses no session (``sessions_migrated`` in the dashboard).

This module is the mesh-level (cross-NAT) serving path at example scale;
datacenter-scale tensor-parallel serving is ``repro.launch.serve``.
"""

from __future__ import annotations

import hashlib
import itertools
from collections import deque
from typing import (Any, Callable, Deque, Dict, Generator, List, Optional,
                    Set, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.dht import PeerInfo
from repro.core.node import LatticaNode
from repro.core.rpc import RpcContext, RpcError
from repro.core.service import (Fixed, RpcStatus, Service, ServiceError,
                                TensorDictCodec, pickled, unary)
from repro.core.simnet import DialError
from repro.models import decoder
from repro.models.common import rms_norm
from repro.models.config import ModelConfig

from .batch import BatchEngine
from .router import LoadAwareRouter

_session_seq = itertools.count(1)
_request_seq = itertools.count(1)


def shard_key(fleet: str, idx: int) -> bytes:
    return hashlib.sha256(f"shard/{fleet}/{idx}".encode()).digest()


def plan_shards(cfg: ModelConfig, n_shards: int) -> List[Tuple[int, int]]:
    """Split layers into contiguous ranges, as even as possible."""
    L = cfg.n_layers
    base, rem = divmod(L, n_shards)
    plan = []
    lo = 0
    for i in range(n_shards):
        hi = lo + base + (1 if i < rem else 0)
        plan.append((lo, hi))
        lo = hi
    return plan


def split_params(cfg: ModelConfig, params: Any,
                 plan: List[Tuple[int, int]]) -> List[Dict[str, Any]]:
    """Per-shard param subsets (first gets embed, last gets norm+head)."""
    shards = []
    for i, (lo, hi) in enumerate(plan):
        sub: Dict[str, Any] = {}
        if decoder.blocks_listed(cfg):
            sub["blocks"] = params["blocks"][lo:hi]
        else:
            sub["blocks"] = jax.tree.map(lambda a: a[lo:hi], params["blocks"])
        if i == 0:
            sub["embed"] = params["embed"]
        if i == len(plan) - 1:
            sub["final_norm"] = params["final_norm"]
            if "lm_head" in params:
                sub["lm_head"] = params["lm_head"]
            elif cfg.tie_embeddings:
                sub["embed_out"] = params["embed"]
        shards.append(sub)
    return shards


def init_shard_params(cfg: ModelConfig, key: jax.Array,
                      plan: List[Tuple[int, int]], i: int,
                      dtype: Any = jnp.float32) -> Dict[str, Any]:
    """Shard ``i`` of ``plan`` initialised alone: bitwise equal to
    ``split_params(cfg, decoder.init_params(cfg, key, dtype), plan)[i]``
    without making the rest of the model.  Arrays land on the current
    default device (``jax.default_device``)."""
    lo, hi = plan[i]
    first, last = i == 0, i == len(plan) - 1
    tied_head = last and cfg.tie_embeddings
    sub = decoder.init_stage(cfg, key, lo, hi, embed=first or tied_head,
                             head=last, dtype=dtype)
    if tied_head:
        sub["embed_out"] = sub["embed"] if first else sub.pop("embed")
    return sub


class ShardModule:
    """Applies one shard's layer range, with per-session decode caches."""

    def __init__(self, cfg: ModelConfig, params: Dict[str, Any],
                 layer_range: Tuple[int, int], is_first: bool, is_last: bool):
        self.cfg = cfg
        self.params = params
        self.lo, self.hi = layer_range
        self.is_first = is_first
        self.is_last = is_last

    @property
    def n_layers(self) -> int:
        return self.hi - self.lo

    def _layer_params(self, j: int) -> Any:
        if decoder.blocks_listed(self.cfg):
            return self.params["blocks"][j]
        return jax.tree.map(lambda a: a[j], self.params["blocks"])

    def embed(self, tokens: jax.Array) -> jax.Array:
        return jnp.take(self.params["embed"], tokens, axis=0)

    def head(self, x: jax.Array) -> jax.Array:
        x = rms_norm(x, self.params["final_norm"], self.cfg.norm_eps)
        w = self.params.get("lm_head")
        if w is None:
            w = self.params["embed_out"].T
        return x @ w

    def init_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        full = decoder.init_cache(self.cfg, batch, max_len)
        if self.cfg.arch == "ssm":
            layers = full["layers"][self.lo:self.hi]
        else:
            layers = jax.tree.map(lambda a: a[self.lo:self.hi], full["layers"])
        return {"len": full["len"], "layers": layers}

    def apply(self, x: jax.Array, positions: jax.Array,
              cache: Optional[Dict[str, Any]],
              rows_out: Optional[List[jax.Array]] = None,
              ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
        """The shard's layers over ``x``; ``rows_out`` collects each MoE
        layer's rows per held expert (configs with ``experts_held``)."""
        cache_len = cache["len"] if cache is not None else None
        new_layers: List[Any] = []
        for j in range(self.n_layers):
            lp = self._layer_params(j)
            lc = (decoder.layer_cache(self.cfg, cache["layers"], j)
                  if cache is not None else None)
            x, nc, _ = decoder.run_block(
                self.cfg, lp, x, positions, lc, cache_len,
                layer_idx=self.lo + j, rows_out=rows_out)
            new_layers.append(nc)
        new_cache = None
        if cache is not None:
            new_cache = {"len": cache_len + x.shape[1],
                         "layers": decoder.stack_layer_caches(self.cfg,
                                                              new_layers)}
        return x, new_cache

    def flops(self, tokens: int) -> float:
        per_layer = 12 * self.cfg.d_model ** 2
        return 2.0 * tokens * per_layer * self.n_layers

    def weight_bytes(self) -> int:
        """Bytes the accelerator streams to apply this shard once — what
        the bandwidth term of the decode cost model charges per pass."""
        return sum(np.prod(leaf.shape) * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(self.params))


class InferenceV2Service(Service):
    """The continuous-batching surface: per-step admission/eviction against
    the shard's slot table.  ``open``/``step`` are *not* idempotent (they
    advance KV caches); ``close``/``stats`` are."""

    name = "infer.v2"

    def __init__(self, server: "ShardServer"):
        self.server = server
        self.scope = f"{server.fleet}.{server.shard_idx}"

    def _check_alive(self) -> None:
        if not self.server.alive:
            raise ServiceError(RpcStatus.UNAVAILABLE,
                               f"shard {self.server.shard_idx} is down")

    @unary("infer.v2.open", request=TensorDictCodec(),
           response=TensorDictCodec(), timeout=120.0)
    def open(self, payload: Any, ctx: RpcContext) -> Generator:
        self._check_alive()
        eng = self.server.engine
        sid = tuple(payload["session"])
        shard = self.server.shard_idx
        tracing.phase(sid, "engine.admit_wait", session=sid, shard=shard)
        out, cost = yield from eng.open(sid, payload["x"], payload["max_len"])
        self._check_alive()     # died while we waited for a slot / computed
        tracing.phase(sid, "rpc.cpu_charge", session=sid, shard=shard,
                      virtual_s=cost)
        yield ctx.cpu(cost)
        eng.touch([sid])
        tracing.phase(sid, "rpc.open.reply", session=sid, shard=shard)
        return {"x": out}

    @unary("infer.v2.step", request=TensorDictCodec(),
           response=TensorDictCodec(), timeout=60.0)
    def step(self, payload: Any, ctx: RpcContext) -> Generator:
        self._check_alive()
        eng = self.server.engine
        sessions = [tuple(s) for s in payload["sessions"]]
        evict = [tuple(s) for s in payload.get("evict", [])]
        out, served, cost = eng.step(sessions, payload["x"], evict=evict)
        lead = sessions[0] if sessions else None     # names the round's flow
        shard = self.server.shard_idx
        tracing.phase(lead, "rpc.cpu_charge", shard=shard, virtual_s=cost)
        yield ctx.cpu(cost)
        eng.touch(served)
        tracing.phase(lead, "rpc.step.reply", shard=shard)
        return {"x": out, "served": served}

    @unary("infer.v2.close", request=pickled(floor=96),
           response=pickled(floor=96), idempotent=True, timeout=15.0)
    def close(self, sessions: Any, ctx: RpcContext) -> Generator:
        yield ctx.cpu(2e-6)
        return self.server.engine.close([tuple(s) for s in sessions])

    @unary("infer.v2.stats", request=Fixed(64), response=pickled(floor=96),
           idempotent=True, timeout=10.0)
    def stats(self, payload: Any, ctx: RpcContext) -> Generator:
        self._check_alive()
        yield ctx.cpu(1e-6)
        eng = self.server.engine
        return {"slots_used": eng.slots_used, "n_slots": eng.n_slots,
                "queue_depth": eng.queue_depth}


class ShardServer:
    def __init__(self, node: LatticaNode, cfg: ModelConfig, fleet: str,
                 shard_idx: int, module: ShardModule, n_slots: int = 8,
                 page_size: int = 32, idle_ttl: float = 300.0,
                 kv_dtype: str = "fp32"):
        if kv_dtype != "fp32":
            raise ValueError(f"pool pages are fp32, not {kv_dtype!r}")
        self.node = node
        self.cfg = cfg
        self.fleet = fleet
        self.shard_idx = shard_idx
        self.module = module
        self.alive = True
        self.idle_ttl = idle_ttl
        self.engine = BatchEngine(module, node.sim, n_slots=n_slots,
                                  page_size=page_size)
        node.serve(InferenceV2Service(self))
        if not hasattr(node, "shard_servers"):
            node.shard_servers = []                      # metrics registry
        node.shard_servers.append(self)
        node.sim.process(self._reaper(), daemon=True)

    def announce(self) -> Generator:
        yield from self.node.dht.provide(shard_key(self.fleet, self.shard_idx))
        return None

    def unannounce(self) -> Generator:
        """Withdraw this replica's DHT provider record (planned retirement
        — the inverse of :meth:`announce`; routers stop finding it)."""
        yield from self.node.dht.unprovide(
            shard_key(self.fleet, self.shard_idx))
        return None

    def stop(self) -> None:
        """Simulate a crash: all subsequent calls fail, and admissions
        parked on the slot queue fail *now* rather than at RPC deadline."""
        self.alive = False
        self.engine.fail_waiters(ServiceError(
            RpcStatus.UNAVAILABLE, f"shard {self.shard_idx} is down"))

    def _reaper(self) -> Generator:
        """Evict slots pinned by vanished clients (crash between steps,
        client-side deadline abandoning a queued admission)."""
        while self.alive:
            yield max(1.0, self.idle_ttl / 2)
            self.engine.reap_idle(self.idle_ttl)
        return None

class _Request:
    """One in-flight generation request inside the client's scheduler."""

    __slots__ = ("rid", "prompt", "n_tokens", "temperature", "rng",
                 "generated", "session", "chain", "done", "attempts",
                 "migrations", "submitted_at", "finished_at", "trace", "flow")

    def __init__(self, prompt: np.ndarray, n_tokens: int, temperature: float,
                 seed: int, done: Any, now: float):
        self.rid = next(_request_seq)
        # host-time spans (repro.core.tracing): the whole request, and the
        # phases up to its first token
        self.trace = tracing.begin("request", request=self.rid,
                                   prompt_tokens=prompt.shape[1],
                                   n_tokens=n_tokens)
        self.flow = tracing.start_flow(request=self.rid, parent=self.trace)
        tracing.phase(self.flow, "client.queue")
        self.prompt = prompt                 # (1, S) int32
        self.n_tokens = n_tokens
        self.temperature = temperature
        self.rng = np.random.default_rng(seed)
        self.generated: List[int] = []
        self.session: Optional[Tuple[str, int]] = None
        self.chain: List[PeerInfo] = []
        self.done = done
        self.attempts = 0
        self.migrations = 0
        self.submitted_at = now
        self.finished_at: Optional[float] = None


class ShardClient:
    """Shard-aware stub: DHT provider resolution, load-aware routing,
    transparent failover, and a continuous-batching driver.

    Its scheduler (``submit``/``generate_concurrent``) multiplexes any
    number of concurrent sessions over one ``infer.v2.step`` RPC per shard
    hop per decode iteration, sampling client-side, and migrates sessions
    off dead providers by replaying prompt ⊕ generated-so-far on a fresh
    chain.
    """

    def __init__(self, node: LatticaNode, cfg: ModelConfig, fleet: str,
                 n_shards: int, resolve_ttl: float = 5.0,
                 max_session_attempts: int = 8, max_migrations: int = 10,
                 on_token: Optional[Callable[..., None]] = None):
        self.node = node
        self.cfg = cfg
        self.fleet = fleet
        self.n_shards = n_shards
        self.resolve_ttl = resolve_ttl
        self.max_session_attempts = max_session_attempts
        self.max_migrations = max_migrations
        #: ``on_token(request, token, logits)``, called for every token
        #: ``submit``'s requests sample, in order; ``request.done`` is the
        #: event ``submit`` returned, ``request.rid`` the id its spans carry
        self.on_token = on_token
        self.router = LoadAwareRouter(node.sim)
        self._providers: Dict[int, List[PeerInfo]] = {}
        self._resolved_at: Dict[int, float] = {}
        self.stats = {"failovers": 0, "calls": 0, "sessions_migrated": 0,
                      "requests": 0, "completed": 0, "failed_sessions": 0}
        self._pending: Deque[_Request] = deque()
        self._admitting: Set[_Request] = set()
        self._active: List[_Request] = []
        self._pump_alive = False
        self._wake: Optional[Any] = None
        if not hasattr(node, "shard_clients"):
            node.shard_clients = []                      # metrics registry
        node.shard_clients.append(self)

    # -- provider resolution -------------------------------------------------
    def _resolve(self, idx: int, refresh: bool = False) -> Generator:
        stale = (self.node.sim.now - self._resolved_at.get(idx, -1e9)
                 > self.resolve_ttl)
        if (refresh or stale or idx not in self._providers
                or not self._providers[idx]):
            provs = yield from self.node.dht.find_providers(
                shard_key(self.fleet, idx))
            fresh = [p for p in provs if p.peer_id != self.node.peer_id]
            if fresh or refresh:
                self._providers[idx] = fresh
            self._resolved_at[idx] = self.node.sim.now
        return self._providers.get(idx, [])

    def _drop_provider(self, idx: int, info: PeerInfo) -> None:
        provs = self._providers.get(idx, [])
        self._providers[idx] = [p for p in provs
                                if p.peer_id != info.peer_id]

    # -- continuous-batching scheduler --------------------------------------
    def submit(self, tokens: np.ndarray, n_tokens: int,
               temperature: float = 0.0, seed: int = 0) -> Any:
        """Enqueue one generation request; returns an Event that succeeds
        with the generated token array (None if the session failed after
        exhausting retries)."""
        prompt = np.asarray(tokens, np.int32).reshape(1, -1)
        req = _Request(prompt, n_tokens, temperature, seed,
                       self.node.sim.event(), self.node.sim.now)
        self.stats["requests"] += 1
        self._pending.append(req)
        self._kick()
        return req.done

    def generate_concurrent(self, requests: List[Dict[str, Any]]) -> Generator:
        """Submit many requests and wait for all; each request is a dict of
        ``submit`` kwargs.  Returns the per-request token arrays."""
        events = [self.submit(**r) for r in requests]
        results = []
        for ev in events:
            res = yield ev
            results.append(res)
        return results

    def _kick(self) -> None:
        if not self._pump_alive:
            self._pump_alive = True
            self.node.sim.process(self._pump())
        elif self._wake is not None and not self._wake.triggered:
            self._wake.succeed()

    def _pump(self) -> Generator:
        """Iteration-level scheduler: start admissions as they arrive, run
        one decode round per iteration over every active session, grouped
        by provider chain (one ``step`` RPC per shard hop per group)."""
        sim = self.node.sim
        try:
            while self._pending or self._admitting or self._active:
                while self._pending:
                    req = self._pending.popleft()
                    self._admitting.add(req)
                    sim.process(self._admit(req))
                if self._active:
                    yield from self._decode_round()
                else:
                    self._wake = sim.event()
                    yield sim.any_of([self._wake, sim.timeout(0.02)])
                    self._wake = None
        finally:
            self._pump_alive = False
        return None

    def _admit(self, req: _Request) -> Generator:
        try:
            status = yield from self._try_admit(req)
        except (RpcError, DialError):
            status = "retry"
        finally:
            self._admitting.discard(req)
        if status == "active":
            self._active.append(req)
        elif status == "retry":
            tracing.phase(req.flow, "client.queue")
            req.attempts += 1
            if req.attempts >= self.max_session_attempts:
                self._fail(req)
            else:
                yield self.node.sim.timeout(0.1 * req.attempts)
                self._pending.append(req)
        self._kick()
        return None

    def _try_admit(self, req: _Request) -> Generator:
        """Route a chain through the shards and prefill (or replay) the
        request on it.  Returns "active", "done", or "retry"."""
        sid = (self.node.host.name, next(_session_seq))
        x: Any = np.concatenate(
            [req.prompt,
             np.asarray(req.generated, np.int32).reshape(1, -1)], axis=1)
        max_len = req.prompt.shape[1] + req.n_tokens + 1
        chain: List[PeerInfo] = []
        tracing.link(req.flow, sid)
        for i in range(self.n_shards):
            tracing.phase(req.flow, "rpc.open.send", session=sid, hop=i)
            provs = yield from self._resolve(i)
            if not provs:
                provs = yield from self._resolve(i, refresh=True)
            resp = None
            for info in self.router.rank(i, list(provs),
                                         lambda p: p.peer_id):
                self.stats["calls"] += 1
                t0 = self.node.sim.now
                self.router.begin(i, info.peer_id)
                try:
                    stub = self.node.stub(InferenceV2Service, info,
                                          scope=f"{self.fleet}.{i}")
                    resp = yield from stub.open(
                        {"session": sid, "x": x, "max_len": max_len})
                    self.router.observe(i, info.peer_id,
                                        self.node.sim.now - t0, True)
                    chain.append(info)
                    break
                except (RpcError, DialError):
                    self.router.observe(i, info.peer_id,
                                        self.node.sim.now - t0, False)
                    self.stats["failovers"] += 1
                    self._drop_provider(i, info)
                finally:
                    self.router.end(i, info.peer_id)
            if resp is None:
                self._spawn_close(sid, chain)
                return "retry"
            x = resp["x"]
        req.session = sid
        req.chain = chain
        req.generated.append(self._sample(req, np.asarray(x)[0]))
        tracing.close_flow(req.flow)
        if len(req.generated) >= req.n_tokens:
            self._finish(req, in_active=False)
            return "done"
        return "active"

    def _decode_round(self) -> Generator:
        groups: Dict[Tuple, List[_Request]] = {}
        for req in list(self._active):
            key = tuple(p.peer_id for p in req.chain)
            groups.setdefault(key, []).append(req)
        procs = [self.node.sim.process(self._step_group(reqs))
                 for reqs in groups.values()]
        for p in procs:
            yield p
        return None

    def _step_group(self, reqs: List[_Request]) -> Generator:
        """One decode iteration for every session pinned to one chain: a
        single batched ``step`` RPC per shard hop.  Providers that died take
        the whole group to migration; sessions a provider no longer holds
        (post-restart) migrate individually via the ``served`` list."""
        chain = reqs[0].chain
        live = list(reqs)
        x: Any = np.asarray([r.generated[-1] for r in live], np.int32)
        # the round's phases per hop; the shards name it by its sessions
        flow = tracing.start_flow()
        try:
            for i, info in enumerate(chain):
                sessions = [r.session for r in live]
                payload = {"sessions": sessions, "x": x}
                tracing.link(flow, *sessions)
                tracing.phase(flow, "rpc.step.send", hop=i, sessions=sessions)
                self.stats["calls"] += 1
                t0 = self.node.sim.now
                self.router.begin(i, info.peer_id)
                try:
                    stub = self.node.stub(InferenceV2Service, info,
                                          scope=f"{self.fleet}.{i}")
                    resp = yield from stub.step(payload)
                    self.router.observe(i, info.peer_id,
                                        self.node.sim.now - t0, True)
                except (RpcError, DialError):
                    self.router.observe(i, info.peer_id,
                                        self.node.sim.now - t0, False)
                    self.stats["failovers"] += 1
                    self._drop_provider(i, info)
                    for r in live:
                        self._migrate(r)
                    return None
                finally:
                    self.router.end(i, info.peer_id)
                served = {tuple(s) for s in resp["served"]}
                missing = [r for r in live if r.session not in served]
                for r in missing:
                    self._migrate(r)
                # response rows align with the engine's served order, which
                # is the payload order filtered to sessions the shard holds
                live = [r for r in live if r.session in served]
                if not live:
                    return None
                x = resp["x"]
        finally:
            tracing.close_flow(flow)
        for r, row in zip(live, x):
            r.generated.append(self._sample(r, row))
            if len(r.generated) >= r.n_tokens:
                self._finish(r)
        return None

    def _sample(self, req: _Request, logits: np.ndarray) -> int:
        with tracing.span("client.sample", flow=req.flow):
            if req.temperature <= 0.0:
                tok = int(np.argmax(logits))
            else:
                z = logits.astype(np.float64) / req.temperature
                z -= z.max()
                p = np.exp(z)
                p /= p.sum()
                tok = int(req.rng.choice(len(p), p=p))
            if self.on_token is not None:
                self.on_token(req, tok, logits)
        return tok

    def _migrate(self, req: _Request) -> None:
        """Provider died (or lost the session) mid-generation: replay
        prompt ⊕ generated on a freshly routed chain.  Client-side sampling
        means no tokens are lost — only the dead shard's KV is recomputed."""
        if req in self._active:
            self._active.remove(req)
        self._spawn_close(req.session, req.chain)
        req.migrations += 1
        self.stats["sessions_migrated"] += 1
        req.session, req.chain = None, []
        if req.migrations > self.max_migrations:
            self._fail(req)
            return
        self._pending.append(req)
        self._kick()

    def _finish(self, req: _Request, in_active: bool = True) -> None:
        if in_active and req in self._active:
            self._active.remove(req)
        req.finished_at = self.node.sim.now
        self._spawn_close(req.session, req.chain)
        self.stats["completed"] += 1
        tracing.end(req.trace, tokens=len(req.generated))
        req.done.succeed(np.asarray(req.generated, np.int32))

    def _fail(self, req: _Request) -> None:
        self.stats["failed_sessions"] += 1
        tracing.close_flow(req.flow)
        tracing.end(req.trace, tokens=len(req.generated), failed=True)
        req.done.succeed(None)

    def _spawn_close(self, sid: Any, chain: List[PeerInfo]) -> None:
        if sid is None or not chain:
            return
        self.node.sim.process(self._close_session(sid, list(chain)))

    def _close_session(self, sid: Any, chain: List[PeerInfo]) -> Generator:
        for i, info in enumerate(chain):
            try:
                stub = self.node.stub(InferenceV2Service, info,
                                      scope=f"{self.fleet}.{i}")
                yield from stub.close([sid])
            except (RpcError, DialError):
                pass              # dead provider needs no eviction
        return None


def deploy_sharded(nodes: List[LatticaNode], cfg: ModelConfig,
                   params: Optional[Any], fleet: str, replicas: int = 1,
                   n_slots: int = 8, page_size: int = 32,
                   init_key: Optional[jax.Array] = None,
                   devices: Optional[List[Any]] = None) -> List[ShardServer]:
    """Place ``n_shards = len(nodes) // replicas`` pipeline shards, each
    replicated ``replicas`` times across the given nodes.

    Servers go round-robin over ``devices`` (default ``jax.devices()``),
    and each server's params live on its device: sliced from the whole
    tree ``params``, or — with ``init_key`` instead — initialised there
    shard by shard (:func:`init_shard_params`), so the whole model never
    exists on one device.  A shard's params exist once per device, shared
    by replicas placed together, and a tied embedding is shared by the
    first and last shards when they share a device."""
    if (params is None) == (init_key is None):
        raise ValueError("give exactly one of params and init_key")
    devices = list(devices or jax.devices())
    n_shards = len(nodes) // replicas
    plan = plan_shards(cfg, n_shards)
    whole = None if params is None else split_params(cfg, params, plan)
    placed: Dict[Tuple[int, Any], Dict[str, Any]] = {}
    embeds: Dict[Any, jax.Array] = {}

    def part_on(i: int, dev: Any) -> Dict[str, Any]:
        if (i, dev) not in placed:
            if whole is not None:
                part = jax.device_put(whole[i], dev)
            else:
                with jax.default_device(dev):
                    part = init_shard_params(cfg, init_key, plan, i)
                # commits the arrays to ``dev`` (same buffers, no copy)
                part = jax.device_put(part, dev)
            for name in ("embed", "embed_out"):
                if name in part and cfg.tie_embeddings:
                    part[name] = embeds.setdefault(dev, part[name])
            placed[(i, dev)] = part
        return placed[(i, dev)]

    servers = []
    for r in range(replicas):
        for i, (lo, hi) in enumerate(plan):
            k = r * n_shards + i
            node = nodes[k]
            module = ShardModule(cfg, part_on(i, devices[k % len(devices)]),
                                 (lo, hi), is_first=(i == 0),
                                 is_last=(i == n_shards - 1))
            servers.append(ShardServer(node, cfg, fleet, i, module,
                                       n_slots=n_slots, page_size=page_size))
    return servers


def serve_fleet(nodes: List[LatticaNode], cfg: ModelConfig, params: Any,
                fleet: str, replicas: int = 1, n_slots: int = 8,
                page_size: int = 32,
                publisher: Optional[LatticaNode] = None) -> Generator:
    """Full serving bring-up: deploy shards, announce DHT providers,
    publish every shard's param sub-DAG + the serving plan into the CRDT
    plane (what :class:`~repro.serving.pressure.PressureMonitor` replicas
    fetch), and start per-server load publishing.  Returns the servers."""
    from .pressure import load_publisher, publish_serving_plan

    servers = deploy_sharded(nodes, cfg, params, fleet, replicas=replicas,
                             n_slots=n_slots, page_size=page_size)
    for s in servers:
        yield from s.announce()
    n_shards = len(servers) // replicas
    plan = plan_shards(cfg, n_shards)
    parts = [s.module.params for s in servers[:n_shards]]
    pub = publisher or nodes[0]
    yield from publish_serving_plan(pub, fleet, plan, parts)
    for s in servers:
        s.node.sim.process(load_publisher(s), daemon=True)
    return servers
