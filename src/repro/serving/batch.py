"""Continuous-batching engine for one pipeline shard (Orca-style).

A :class:`BatchEngine` owns a fixed table of decode *slots*.  Each slot
holds one session's KV cache, allocated in pages of ``page_size`` tokens
and grown on demand, so a shard admits new sequences and evicts finished
ones at every decode step — prefill and decode interleave across
concurrent sessions instead of queueing whole requests.

Admission is FIFO: when the slot table is full, ``open`` parks the caller
on a queue event and a freed slot is handed directly to the oldest
waiter (no barging).  The engine is deliberately yield-free apart from
that admission wait; compute methods return a simulated *cost in
seconds* alongside the result so the RPC handler charges CPU time
*once per batched call*.

Two decode paths share the slot table:

* **Fused paged decode** (attention-family archs: dense/moe/vlm/audio,
  no mrope, no sliding window).  KV lives in an engine-owned *page
  pool* — per layer ``(P, page, Hk, hd)`` numpy arrays plus a free-page
  list — and each slot holds a block table of page ids.  One jitted
  forward advances *every* live slot per step: per layer, project
  q/k/v for the whole batch, run paged single-query attention
  (:mod:`repro.kernels.paged_attention`) over the block tables, and
  return the new k/v rows, which the engine appends into the pool
  host-side.  The unfused path re-reads the shard weights once per
  session per token; the fused path reads them once per *batch* — in a
  roofline cost model that is where batched decode actually wins.
  ``kv_dtype="int8"`` stores pool pages quantized (per-page per-kv-head
  scales, dequantized inside the attention kernel) for ~4x fewer
  cache-resident bytes; the partial (current) page keeps an fp32
  staging master per slot, so requantization never compounds error.

* **Per-slot fallback** (ssm/hybrid/mrope/windowed): the original
  batch=1 ``module.apply`` loop with whole-page dense cache growth,
  numerics bit-identical to the v1 path.

Page accounting is exact in both paths: the pool's free list makes
alloc/free symmetric by construction, the fallback keeps a running
counter (no O(slots) rescans on grow), and ``stats["pages"]`` always
equals pages currently in use (0 when every session is closed).

The fp32 fused path is argmax-equivalent to the v1 path (same
projection/rope/mask/softmax formulation on the same cached values), so
greedy decode through the batched plane still matches
:class:`repro.serving.engine.GenerationEngine`.
"""

from __future__ import annotations

import copy
import itertools
from collections import deque
from typing import Any, Deque, Dict, Generator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.simnet import Sim
from repro.kernels.paged_attention import paged_attention_jnp
from repro.models.common import apply_rope, rms_norm, run_mlp
from repro.models.moe import run_moe

__all__ = ["BatchEngine", "KVPool", "SlotState", "PEER_FLOPS", "PEER_BW"]

#: assumed accelerator throughput per serving peer, for simulated latency
PEER_FLOPS = 2.0e11
#: assumed accelerator memory bandwidth per serving peer (bytes/s); decode
#: is bandwidth-bound, so step cost is max(compute, weight+KV traffic)
PEER_BW = 8.0e10

#: archs the fused paged-decode path supports (attention-family blocks)
_FUSED_ARCHS = ("dense", "moe", "vlm", "audio")

#: distinguishes each engine's simsan leak gauge within one Sim
_ENGINE_SEQ = itertools.count()


def _quant_page_int8(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric int8 quantization of one page ``(L, page, Hk, hd)`` with
    per-(layer, kv-head) scales: |x - x̂| <= absmax/254 elementwise."""
    amax = np.abs(x).max(axis=(1, 3))
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.rint(x / scale[:, None, :, None]).astype(np.int8)
    return q, scale


class KVPool:
    """Shared paged KV storage for one shard's fused decode path.

    Per layer ``k/v`` pools of shape ``(L, P, page, Hk, hd)`` grown
    geometrically, plus a free-page list — alloc and free are exact and
    symmetric.  ``quant`` stores int8 pages with per-(page, kv-head)
    dequant scales ``(L, P, Hk)``.
    """

    def __init__(self, n_layers: int, n_kv_heads: int, head_dim: int,
                 page_size: int, quant: bool = False):
        self.L = n_layers
        self.Hk = n_kv_heads
        self.hd = head_dim
        self.page = page_size
        self.quant = quant
        self.n_pages = 0
        self._free: List[int] = []
        dt = np.int8 if quant else np.float32
        self.kp = np.zeros((self.L, 0, page_size, self.Hk, self.hd), dt)
        self.vp = np.zeros_like(self.kp)
        self.ks = (np.ones((self.L, 0, self.Hk), np.float32)
                   if quant else None)
        self.vs = (np.ones((self.L, 0, self.Hk), np.float32)
                   if quant else None)

    @property
    def page_bytes(self) -> int:
        """Cache-resident bytes of one allocated page (k+v, + scales)."""
        per = self.L * self.page * self.Hk * self.hd * self.kp.dtype.itemsize
        scales = 2 * self.L * self.Hk * 4 if self.quant else 0
        return 2 * per + scales

    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free)

    def bytes_in_use(self) -> int:
        return self.pages_in_use() * self.page_bytes

    def _grow(self, min_total: int) -> None:
        total = max(min_total, self.n_pages * 2, 8)
        add = total - self.n_pages

        def ext(a: np.ndarray, fill: float = 0.0) -> np.ndarray:
            blk = np.full((self.L, add) + a.shape[2:], fill, a.dtype)
            return np.concatenate([a, blk], axis=1)

        self.kp = ext(self.kp)
        self.vp = ext(self.vp)
        if self.quant:
            self.ks = ext(self.ks, 1.0)
            self.vs = ext(self.vs, 1.0)
        self._free.extend(range(self.n_pages, total))
        self.n_pages = total

    def alloc(self, n: int) -> List[int]:
        if len(self._free) < n:
            self._grow(self.n_pages + n - len(self._free))
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: List[int]) -> None:
        self._free.extend(pages)

    def write_page(self, pid: int, k: np.ndarray, v: np.ndarray) -> None:
        """Store one full page ``(L, page, Hk, hd)`` fp32 (zero-padded
        past the valid tokens — zeros quantize to 0 under any scale)."""
        if self.quant:
            self.kp[:, pid], self.ks[:, pid] = _quant_page_int8(k)
            self.vp[:, pid], self.vs[:, pid] = _quant_page_int8(v)
        else:
            self.kp[:, pid] = k
            self.vp[:, pid] = v

    def write_tokens(self, pid: int, offset: int, k: np.ndarray,
                     v: np.ndarray) -> None:
        """fp32 pools only: in-place write of ``t`` tokens at ``offset``."""
        t = k.shape[1]
        self.kp[:, pid, offset:offset + t] = k
        self.vp[:, pid, offset:offset + t] = v


def _with_params(module: Any, params: Any) -> Any:
    """A shallow copy of ``module`` that reads ``params``: lets a jitted
    function take the weights as an argument.  A closed-over array would
    be embedded in the executable as a constant — at published widths,
    gigabytes of literals in every compiled program."""
    bound = copy.copy(module)
    bound.params = params
    return bound


class SlotState:
    """One occupied decode slot: a session pinned to a paged KV cache."""

    __slots__ = ("session", "slot", "cache", "capacity", "max_len",
                 "last_used", "length", "pages", "k_tail", "v_tail")

    def __init__(self, session: Any, slot: int, cache: Optional[Dict[str, Any]],
                 capacity: int, max_len: int, now: float):
        self.session = session
        self.slot = slot
        self.cache = cache            # dense per-slot cache (fallback path)
        self.capacity = capacity
        self.max_len = max_len
        self.last_used = now
        self.length = 0               # cached tokens (fused path)
        self.pages: List[int] = []    # pool page ids (fused path)
        self.k_tail: Optional[np.ndarray] = None   # fp32 staging master for
        self.v_tail: Optional[np.ndarray] = None   # the partial page (int8)


def _fused_block(cfg: Any, p: Any, x: jax.Array, positions: jax.Array,
                 bt: jax.Array, lengths: jax.Array, kp: jax.Array,
                 vp: jax.Array, ks: Optional[jax.Array],
                 vs: Optional[jax.Array],
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One attention-family block for a batch of single-token rows, with
    KV read from the page pool.  Mirrors ``decoder.run_block``'s dense
    decode math exactly (rms_norm -> q/k/v -> qk_norm -> rope -> masked
    softmax over the cache -> wo -> residual -> ln2 -> mlp/moe)."""
    ap = p["attn"]
    B = x.shape[0]
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = (h @ ap["wq"]).reshape(B, 1, H, hd)
    k = (h @ ap["wk"]).reshape(B, 1, Hk, hd)
    v = (h @ ap["wv"]).reshape(B, 1, Hk, hd)
    if cfg.qk_norm:
        q = rms_norm(q, ap["q_norm"], cfg.norm_eps)
        k = rms_norm(k, ap["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    attn = paged_attention_jnp(q[:, 0], kp, vp, bt, lengths,
                               k[:, 0], v[:, 0], ks, vs)     # (B, H, hd)
    x = x + attn.reshape(B, 1, H * hd) @ ap["wo"]
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.arch == "moe":
        ffn, _ = run_moe(p["moe"], cfg, h2, use_kernel=cfg.use_flash_kernel,
                         no_drop=True)
    else:
        ffn = run_mlp(p["mlp"], h2)
    return x + ffn, k[:, 0], v[:, 0]


class BatchEngine:
    def __init__(self, module: Any, sim: Sim, n_slots: int = 8,
                 page_size: int = 32, kv_dtype: str = "fp32",
                 fused: Optional[bool] = None):
        if kv_dtype not in ("fp32", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
        self.module = module
        self.sim = sim
        self.n_slots = n_slots
        self.page_size = page_size
        self._free: List[int] = list(range(n_slots - 1, -1, -1))
        self._slot_last_session: List[Any] = [None] * n_slots
        self.by_session: Dict[Any, SlotState] = {}
        # FIFO of (session, event) waiting for a slot; a freed slot is
        # succeed()ed straight into the head waiter's event
        self._queue: Deque[Tuple[Any, Any]] = deque()
        # params are jit arguments (never closed over); shapes key the
        # trace cache, so steady-state decode is one compiled call per shape.
        # Named, so that prefill shows in a profile as ``jit_dense_apply``.
        def dense_apply(params, x, pos, cache):
            return _with_params(module, params).apply(x, pos, cache)

        self._apply = jax.jit(dense_apply)
        supported = self._supports_fused(module)
        self.fused = supported if fused is None else (fused and supported)
        self.kv_dtype = kv_dtype if self.fused else "fp32"
        self._pool: Optional[KVPool] = None
        self._fallback_pages = 0      # exact page counter for the dense path
        if self.fused:
            cfg = module.cfg
            self._pool = KVPool(module.n_layers, cfg.n_kv_heads, cfg.hd,
                                page_size, quant=(self.kv_dtype == "int8"))
            self._fused_apply = jax.jit(self._build_fused_apply())
        self.stats = {
            "admitted": 0, "evicted": 0, "steps": 0,
            "step_sessions": 0, "queue_peak": 0, "slot_reuse": 0,
            "pages": 0, "pages_peak": 0, "idle_evicted": 0,
            # fused steps: pool bytes handed to the device, and of those the
            # live rows' cached bytes
            "kv_bytes_uploaded": 0, "kv_bytes_live": 0,
        }
        sim.register_leak_check(
            f"kv.pages:{next(_ENGINE_SEQ)}", self._pages_in_use)

    @staticmethod
    def _supports_fused(module: Any) -> bool:
        cfg = getattr(module, "cfg", None)
        return (cfg is not None
                and cfg.arch in _FUSED_ARCHS
                and not cfg.mrope
                and cfg.window == 0
                and hasattr(module, "_layer_params"))

    def _build_fused_apply(self):
        cfg = self.module.cfg

        def fused(params, x, positions, bt, lengths, kp, vp, ks, vs):
            m = _with_params(self.module, params)
            if m.is_first and x.dtype == jnp.int32:
                h = m.embed(x[:, None])                      # (M, 1, D)
            else:
                h = x[:, None, :]
            new_k: List[jax.Array] = []
            new_v: List[jax.Array] = []
            for j in range(m.n_layers):
                lp = m._layer_params(j)
                h, kn, vn = _fused_block(
                    cfg, lp, h, positions, bt, lengths, kp[j], vp[j],
                    None if ks is None else ks[j],
                    None if vs is None else vs[j])
                new_k.append(kn)
                new_v.append(vn)
            out = m.head(h)[:, 0] if m.is_last else h[:, 0]
            return out, jnp.stack(new_k), jnp.stack(new_v)

        return fused

    # -- occupancy (what pressure publishing reports) -----------------------
    @property
    def slots_used(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    # -- paged cache --------------------------------------------------------
    def _pages_for(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.page_size))

    def _alloc_cache(self, n_tokens: int) -> Tuple[Dict[str, Any], int]:
        cap = self._pages_for(n_tokens) * self.page_size
        cache = self.module.init_cache(1, cap)
        return cache, cap

    def _ensure_capacity(self, st: SlotState, need: int) -> None:
        """Grow the slot's dense cache by whole pages until it can hold
        ``need`` tokens.  Growth pads each leaf along its (single)
        capacity axis, so it is arch-agnostic: SSM/recurrent leaves keep
        their shapes and window-limited caches stop growing at the
        window."""
        if need <= st.capacity:
            return
        new_cap = self._pages_for(need) * self.page_size
        fresh = self.module.init_cache(1, new_cap)

        def merge(old: jax.Array, new: jax.Array) -> jax.Array:
            if old.shape == new.shape:
                return old
            diff = [d for d in range(old.ndim) if old.shape[d] != new.shape[d]]
            assert len(diff) == 1, (old.shape, new.shape)
            ax = diff[0]
            pad = [(0, new.shape[d] - old.shape[d]) if d == ax else (0, 0)
                   for d in range(old.ndim)]
            return jnp.pad(old, pad)

        grown = jax.tree.map(merge, st.cache["layers"], fresh["layers"])
        st.cache = {"len": st.cache["len"], "layers": grown}
        self._fallback_pages += (new_cap - st.capacity) // self.page_size
        st.capacity = new_cap
        self._note_pages()

    def _pages_in_use(self) -> int:
        if self.fused:
            return self._pool.pages_in_use()
        return self._fallback_pages

    def _note_pages(self) -> None:
        used = self._pages_in_use()
        self.stats["pages"] = used
        if used > self.stats["pages_peak"]:
            self.stats["pages_peak"] = used

    # -- cost model ---------------------------------------------------------
    def _weight_bytes(self) -> float:
        wb = getattr(self.module, "weight_bytes", None)
        if callable(wb):
            return float(wb())
        # flops(1) = 2 * params-touched; fp32 params = 2 bytes per flop
        return 2.0 * self.module.flops(1)

    def _slot_kv_bytes(self, st: SlotState) -> float:
        if self.fused:
            b = len(st.pages) * self._pool.page_bytes
            if st.k_tail is not None:
                b += st.k_tail.nbytes + st.v_tail.nbytes
            return float(b)
        if st.cache is None:
            return 0.0
        return float(sum(leaf.nbytes
                         for leaf in jax.tree.leaves(st.cache["layers"])))

    def kv_bytes(self) -> float:
        """Current cache-resident bytes across all live slots (pool pages
        + fp32 staging tails, or dense per-slot caches)."""
        if self.fused:
            b = float(self._pool.bytes_in_use())
            for st in self.by_session.values():
                if st.k_tail is not None:
                    b += st.k_tail.nbytes + st.v_tail.nbytes
            return b
        return sum(self._slot_kv_bytes(st) for st in self.by_session.values())

    def _cost(self, flops: float, bytes_moved: float) -> float:
        """Roofline step time: compute-bound or bandwidth-bound."""
        return max(flops / PEER_FLOPS, bytes_moved / PEER_BW)

    # -- admission / eviction ------------------------------------------------
    def open(self, session: Any, x: np.ndarray, max_len: int) -> Generator:
        """Admit ``session`` (waiting FIFO for a slot if the table is full)
        and run its prefill.  Returns ``(out, cost_seconds)``; idempotent
        per session id — re-opening replaces the previous cache (and frees
        its pages), so a retried admission cannot leak a slot or a page."""
        if session in self.by_session:
            old = self.by_session.pop(session)
            slot = old.slot
            self._free_slot_storage(old)
        elif self._free:
            slot = self._free.pop()
        else:
            ev = self.sim.event()
            self._queue.append((session, ev))
            self.stats["queue_peak"] = max(self.stats["queue_peak"],
                                           len(self._queue))
            slot = yield ev
        out, cost = self._prefill(session, slot, x, max_len)
        return out, cost

    def close(self, sessions: List[Any]) -> int:
        n = 0
        for sid in list(sessions):
            if sid in self.by_session:
                self._release(sid)
                n += 1
        return n

    def reap_idle(self, ttl: float) -> int:
        """Evict sessions untouched for ``ttl`` sim-seconds (crashed or
        timed-out clients must not pin slots forever)."""
        now = self.sim.now
        stale = [sid for sid, st in self.by_session.items()
                 if now - st.last_used > ttl]
        for sid in stale:
            self._release(sid)
            self.stats["idle_evicted"] += 1
        return len(stale)

    def fail_waiters(self, exc: BaseException) -> int:
        """Crash path: wake every queued admission with ``exc``.  A dead
        server must not pin parked callers until their RPC deadline — the
        error surfaces immediately so the client re-admits elsewhere."""
        n = 0
        while self._queue:
            _, ev = self._queue.popleft()
            ev.fail(exc)
            n += 1
        return n

    def _free_slot_storage(self, st: SlotState) -> None:
        """Return a slot's cache storage (not the slot itself)."""
        if self.fused:
            self._pool.free(st.pages)
            st.pages = []
        else:
            self._fallback_pages -= st.capacity // self.page_size
        self._note_pages()

    def _release(self, session: Any) -> None:
        st = self.by_session.pop(session)
        self.stats["evicted"] += 1
        self._free_slot_storage(st)
        if self._queue:
            _, ev = self._queue.popleft()
            ev.succeed(st.slot)       # direct handoff keeps admission FIFO
        else:
            self._free.append(st.slot)

    # -- compute ------------------------------------------------------------
    def _positions(self, base: Any, B: int, S: int) -> jax.Array:
        if S == 1:
            pos = jnp.broadcast_to(jnp.asarray(base)[None, None],
                                   (B, 1)).astype(jnp.int32)
        else:
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                   (B, S))
        if self.module.cfg.mrope:
            pos = jnp.broadcast_to(pos[None], (3,) + pos.shape)
        return pos

    def _pool_write_prefill(self, st: SlotState, k: np.ndarray,
                            v: np.ndarray) -> None:
        """Copy a prefilled slot's k/v ``(L, S, Hk, hd)`` into its pool
        pages; the partial last page keeps an fp32 staging master when
        the pool is quantized (appends requantize from it, so error never
        compounds)."""
        pool, page = self._pool, self.page_size
        L, S = k.shape[0], k.shape[1]
        n_full = S // page
        for pi in range(n_full):
            sl = slice(pi * page, (pi + 1) * page)
            pool.write_page(st.pages[pi], k[:, sl], v[:, sl])
        rem = S - n_full * page
        if pool.quant:
            st.k_tail = np.zeros((L, page) + k.shape[2:], np.float32)
            st.v_tail = np.zeros_like(st.k_tail)
            if rem:
                st.k_tail[:, :rem] = k[:, n_full * page:]
                st.v_tail[:, :rem] = v[:, n_full * page:]
                pool.write_page(st.pages[n_full], st.k_tail, st.v_tail)
        elif rem:
            pool.write_tokens(st.pages[n_full], 0,
                              k[:, n_full * page:], v[:, n_full * page:])

    def _pool_append(self, st: SlotState, kn: np.ndarray,
                     vn: np.ndarray) -> None:
        """Append one token's k/v ``(L, Hk, hd)`` at position
        ``st.length`` (the page was allocated before the fused call)."""
        pool, page = self._pool, self.page_size
        pos = st.length
        off = pos % page
        pid = st.pages[pos // page]
        if pool.quant:
            if off == 0:
                st.k_tail[:] = 0.0
                st.v_tail[:] = 0.0
            st.k_tail[:, off] = kn
            st.v_tail[:, off] = vn
            pool.write_page(pid, st.k_tail, st.v_tail)
        else:
            pool.kp[:, pid, off] = kn
            pool.vp[:, pid, off] = vn
        st.length = pos + 1

    def _prefill(self, session: Any, slot: int, x: np.ndarray,
                 max_len: int) -> Tuple[np.ndarray, float]:
        m = self.module
        with tracing.span("engine.prefill", flow=session, session=session,
                          tokens=int(x.shape[1])):
            self.stats["admitted"] += 1
            if self._slot_last_session[slot] not in (None, session):
                self.stats["slot_reuse"] += 1
            self._slot_last_session[slot] = session
            xj = jnp.asarray(x)
            if m.is_first and xj.dtype == jnp.int32:
                xj = m.embed(xj)
            S = xj.shape[1]
            cache, cap = self._alloc_cache(S + 1)
            st = SlotState(session, slot, cache, cap, max_len, self.sim.now)
            self.by_session[session] = st
            if self.fused:
                # prefill runs through the unchanged dense path, then the
                # resulting k/v move into pool pages and the dense cache is
                # dropped — steady-state decode never touches it again
                out, cache = self._apply(m.params, xj,
                                         self._positions(0, 1, S), cache)
                st.cache = None
                st.length = S
                st.pages = self._pool.alloc(cap // self.page_size)
                k = np.asarray(cache["layers"]["k"][:, 0, :S], np.float32)
                v = np.asarray(cache["layers"]["v"][:, 0, :S], np.float32)
                with tracing.span("kv.write_prefill", pages=len(st.pages)):
                    self._pool_write_prefill(st, k, v)
            else:
                self._fallback_pages += cap // self.page_size
                out, st.cache = self._apply(m.params, xj,
                                            self._positions(0, 1, S),
                                            st.cache)
            self._note_pages()
            if m.is_last:
                out = m.head(out[:, -1:])[:, 0]       # (1, vocab)
            cost = self._cost(m.flops(S),
                              self._weight_bytes() + self._slot_kv_bytes(st))
            return np.asarray(out), cost

    def step(self, sessions: List[Any], x: np.ndarray,
             evict: Optional[List[Any]] = None,
             ) -> Tuple[np.ndarray, List[Any], float]:
        """One decode iteration over a batch of sessions.

        ``x`` is row-aligned with ``sessions``: int32 token ids ``(M,)``
        on the first shard, activations ``(M, d_model)`` downstream.
        Sessions the engine no longer holds are skipped rather than
        failing the whole batch; the returned ``served`` list tells the
        driver which rows came back (missing ones get migrated).
        ``evict`` frees finished sessions *before* compute, so their
        slots are available to queued admissions within the same step.
        Returns ``(out, served, cost_seconds)``.
        """
        lead = sessions[0] if sessions else None    # names the round's flow
        with tracing.span("engine.step", flow=lead,
                          sessions=len(sessions)) as sp:
            if evict:
                self.close(evict)
            self.stats["steps"] += 1
            if self.fused:
                out, served, cost = self._step_fused(sessions, x)
            else:
                out, served, cost = self._step_unfused(sessions, x)
            sp.set(rows=len(served))
            return out, served, cost

    def _step_fused(self, sessions: List[Any], x: np.ndarray,
                    ) -> Tuple[np.ndarray, List[Any], float]:
        m = self.module
        xa = np.asarray(x)
        live: List[Tuple[int, Any, SlotState]] = []
        for i, sid in enumerate(sessions):
            st = self.by_session.get(sid)
            if st is None:
                continue
            st.last_used = self.sim.now
            need = self._pages_for(st.length + 1)
            if need > len(st.pages):           # next token starts a new page
                st.pages.extend(self._pool.alloc(need - len(st.pages)))
                st.capacity = len(st.pages) * self.page_size
                self._note_pages()
            live.append((i, sid, st))
        if not live:
            return np.zeros((0, 1), dtype=np.float32), [], 0.0
        # fixed-width batch: rows padded to n_slots, block tables padded to
        # the next power of two, so jit retraces only on pool/table growth
        M = self.n_slots
        np_pad = 1
        np_need = max(len(st.pages) for _, _, st in live)
        while np_pad < np_need:
            np_pad *= 2
        tokens = m.is_first and np.issubdtype(xa.dtype, np.integer)
        xb = (np.zeros((M,), np.int32) if tokens
              else np.zeros((M,) + xa.shape[1:], np.float32))
        bt = np.zeros((M, np_pad), np.int32)
        lengths = np.zeros((M,), np.int32)
        for r, (i, _, st) in enumerate(live):
            xb[r] = xa[i]
            bt[r, :len(st.pages)] = st.pages
            lengths[r] = st.length
        pool = self._pool
        # the whole pool goes to the device every step; the live rows'
        # pages (allocated above, so appends do not change them) are the
        # part the step reads
        uploaded = sum(a.nbytes for a in (pool.kp, pool.vp, pool.ks, pool.vs)
                       if a is not None)
        kv_read = sum(self._slot_kv_bytes(st) for _, _, st in live)
        self.stats["kv_bytes_uploaded"] += uploaded
        self.stats["kv_bytes_live"] += int(kv_read)
        # the step's inputs go to the device before the pool, as they did
        # before the spans: a small copy queued behind the pool's would wait
        # for it outside the pool's copy
        inputs = (jnp.asarray(xb), jnp.asarray(lengths[:, None]),
                  jnp.asarray(bt), jnp.asarray(lengths))
        with tracing.span("kv.upload", bytes=uploaded,
                          live_bytes=int(kv_read)):
            kp, vp = jnp.asarray(pool.kp), jnp.asarray(pool.vp)
            ks = None if pool.ks is None else jnp.asarray(pool.ks)
            vs = None if pool.vs is None else jnp.asarray(pool.vs)
        with tracing.span("engine.fused", rows=len(live)):
            out, nk, nv = self._fused_apply(m.params, *inputs,
                                            kp, vp, ks, vs)
            out = np.asarray(out)
            nk = np.asarray(nk, np.float32)
            nv = np.asarray(nv, np.float32)
        served: List[Any] = []
        with tracing.span("kv.append"):
            for r, (_, sid, st) in enumerate(live):
                self._pool_append(st, nk[:, r], nv[:, r])
                served.append(sid)
        self.stats["step_sessions"] += len(served)
        # one pass over the weights for the whole batch — the fused win
        cost = self._cost(m.flops(1) * len(served),
                          self._weight_bytes() + kv_read)
        return out[:len(live)], served, cost

    def _step_unfused(self, sessions: List[Any], x: np.ndarray,
                      ) -> Tuple[np.ndarray, List[Any], float]:
        m = self.module
        served: List[Any] = []
        outs: List[np.ndarray] = []
        cost = 0.0
        for i, sid in enumerate(sessions):
            st = self.by_session.get(sid)
            if st is None:
                continue
            st.last_used = self.sim.now
            xi = jnp.asarray(x[i])[None]          # (1,) tokens or (1, D)
            if m.is_first and xi.dtype == jnp.int32:
                xi = m.embed(xi[:, None])
            else:
                xi = xi[:, None]                  # (1, 1, D)
            cur = int(st.cache["len"])
            self._ensure_capacity(st, cur + 1)
            out, st.cache = self._apply(
                m.params, xi, self._positions(cur, 1, 1), st.cache)
            if m.is_last:
                out = m.head(out)[:, 0]           # (1, vocab)
            else:
                out = out[:, 0]                   # (1, d_model)
            outs.append(np.asarray(out[0]))
            served.append(sid)
            # every session re-reads the shard weights: M passes per step
            cost += self._cost(m.flops(1),
                               self._weight_bytes() + self._slot_kv_bytes(st))
        self.stats["step_sessions"] += len(served)
        out_arr = (np.stack(outs) if outs
                   else np.zeros((0, 1), dtype=np.float32))
        return out_arr, served, cost

    def slot_of(self, session: Any) -> Optional[int]:
        st = self.by_session.get(session)
        return None if st is None else st.slot
