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
  pool* — per layer ``(P, page, Hk, hd)`` arrays resident on the
  shard's device, plus a free-page list — and each slot holds a block
  table of page ids.  One jitted forward advances *every* live slot per
  step: per layer, project q/k/v for the whole batch, run paged
  single-query attention (:mod:`repro.kernels.paged_attention`) over
  the block tables, and return the new k/v rows, which one donated
  scatter then writes into the pool in place.  Latent-attention (MLA)
  configs keep one row of ``latent_dim`` numbers per token and layer
  instead of separate k and v (split into a latent and a rope-key array
  of whole lane tiles where the widths allow, so that TPU scatters
  write it in place), and decode with ``W_UK`` absorbed into
  the query (:mod:`repro.models.mla`); a shard's layers may differ in
  kind (a leading dense SwiGLU, then MoE layers running this device's
  share of the experts).  Prefill writes its pages
  the same way, straight from the dense cache on the device: no step
  copies the pool between host and device.  The unfused path re-reads
  the shard weights once per session per token; the fused path reads
  them once per *batch* — in a roofline cost model that is where
  batched decode actually wins.  Pool pages are float32: one KV format
  per attention kind.

* **Per-slot fallback** (ssm/hybrid/mrope/windowed): the original
  batch=1 ``module.apply`` loop with whole-page dense cache growth,
  numerics bit-identical to the model's own dense decode.

Page accounting is exact in both paths: the pool's free list makes
alloc/free symmetric by construction, the fallback keeps a running
counter (no O(slots) rescans on grow), and ``stats["pages"]`` always
equals pages currently in use (0 when every session is closed).

The fused path is argmax-equivalent to the dense decode path (same
projection/rope/mask/softmax formulation on the same cached values), so
greedy decode through the batched plane still matches
:class:`repro.serving.engine.GenerationEngine`.
"""

from __future__ import annotations

import copy
import functools
import itertools
from collections import deque
from typing import Any, Deque, Dict, Generator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.simnet import Sim
from repro.kernels.paged_attention import (paged_attention_jnp,
                                           paged_latent_attention_jnp)
from repro.models import mla
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


def _latent_lanes(rank: int, rope: int, page: int) -> int:
    """Tokens per rope slab of a latent pool laid out in whole lane tiles
    (``rank`` latent numbers as ``(rank // 128, 128)``, ``g = 256 // rope``
    tokens' rope keys per ``(2, 128)`` slab), or 0 where the widths do not
    fit that layout and the pool keeps one ``(1, rank + rope)`` row."""
    if (rank <= 0 or rope <= 0 or rank % 128 or 256 % rope
            or page % (256 // rope)):
        return 0
    return 256 // rope


def _device_of(params: Any) -> Any:
    """The device that holds ``params`` (None: JAX's default device)."""
    for leaf in jax.tree.leaves(params):
        if isinstance(leaf, jax.Array):
            return next(iter(leaf.devices()))
    return None


class KVPool:
    """Shared paged KV storage for one shard's fused decode path.

    Per layer ``k/v`` pools of shape ``(L, P, page, Hk, hd)``, held on
    ``device`` and grown geometrically, plus a free-page list — alloc and
    free are exact and symmetric.  ``latent`` pools (MLA) hold one row
    of ``head_dim`` numbers per token and layer, the latent ``c`` then
    ``rope_dim`` numbers of rope key, and no values.  Where the widths
    allow (``_latent_lanes``) the row is split into whole lane tiles,
    which TPU scatters write in place: ``kp`` holds ``c`` as ``(L, P,
    page, r // 128, 128)`` and ``vp`` the rope keys as ``(L, P, page //
    g, 2, 128)``, g tokens a slab.  Otherwise ``kp`` is
    ``(L, P, page, 1, head_dim)`` and ``vp`` is None.  Writes replace the
    arrays with the outputs of donated in-place scatters
    (``_write_prefill``, ``_append_rows``); nothing copies the pool.
    """

    def __init__(self, n_layers: int, n_kv_heads: int, head_dim: int,
                 page_size: int, device: Any = None, latent: bool = False,
                 rope_dim: int = 0):
        if latent and n_kv_heads != 1:
            raise ValueError("latent pools are rows of one 'head'")
        self.L = n_layers
        self.Hk = n_kv_heads
        self.hd = head_dim
        self.page = page_size
        self.latent = latent
        self.device = device
        self.n_pages = 0
        self._free: List[int] = []
        dt = jnp.float32                # one KV format: float32 pages
        shape = (self.L, 0, page_size, self.Hk, self.hd)
        rank = head_dim - rope_dim
        g = _latent_lanes(rank, rope_dim, page_size) if latent else 0
        if g:
            self.kp = jnp.zeros((self.L, 0, page_size, rank // 128, 128), dt,
                                device=device)
            self.vp = jnp.zeros((self.L, 0, page_size // g, 2, 128), dt,
                                device=device)
        else:
            self.kp = jnp.zeros(shape, dt, device=device)
            self.vp = None if latent else jnp.zeros(shape, dt, device=device)

    @property
    def arrays(self) -> Tuple[Any, ...]:
        return self.kp, self.vp

    @arrays.setter
    def arrays(self, new: Tuple[Any, ...]) -> None:
        self.kp, self.vp = new

    @property
    def _arrays_per_row(self) -> int:
        return 1 if self.latent else 2

    @property
    def page_bytes(self) -> int:
        """Cache-resident bytes of one allocated page (k+v; a latent
        pool's rows alone)."""
        return self.page * self.append_bytes

    @property
    def append_bytes(self) -> int:
        """Pool bytes one appended token writes: its k/v (or latent) row."""
        return (self._arrays_per_row * self.L * self.Hk * self.hd
                * self.kp.dtype.itemsize)

    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free)

    def bytes_in_use(self) -> int:
        return self.pages_in_use() * self.page_bytes

    def _grow(self, min_total: int) -> None:
        total = max(min_total, self.n_pages * 2, 8)
        add = total - self.n_pages

        def ext(a: jax.Array) -> jax.Array:
            blk = jnp.zeros((self.L, add) + a.shape[2:], a.dtype,
                            device=self.device)
            # an empty pool takes the block as it is: a concatenation would
            # hold the new pages twice on the device for a moment
            return jnp.concatenate([a, blk], axis=1) if a.shape[1] else blk

        self.kp = ext(self.kp)
        if self.vp is not None:
            self.vp = ext(self.vp)
        self._free.extend(range(self.n_pages, total))
        self.n_pages = total

    def alloc(self, n: int) -> List[int]:
        if len(self._free) < n:
            self._grow(self.n_pages + n - len(self._free))
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: List[int]) -> None:
        self._free.extend(pages)


def _write_latent_lanes(kp: jax.Array, vp: jax.Array, pages: jax.Array,
                        rows: jax.Array) -> Tuple[Any, ...]:
    """Write the latent rows ``(L, cap, W)`` of ``cap // page`` whole
    ``pages`` into a lane-layout pool: each token's ``c`` by a token-row
    scatter, its rope key with the g tokens of its slab, slab by slab.
    Both scatters write in place; a scatter of whole pages would not."""
    L, cap, _ = rows.shape
    n, page, slabs = pages.shape[0], kp.shape[2], vp.shape[2]
    r = kp.shape[-2] * kp.shape[-1]
    pg, off = jnp.repeat(pages, page), jnp.tile(jnp.arange(page), n)
    kp = kp.at[:, pg, off].set(rows[..., :r].reshape((L, cap) + kp.shape[-2:]))
    pg, off = jnp.repeat(pages, slabs), jnp.tile(jnp.arange(slabs), n)
    vp = vp.at[:, pg, off].set(
        rows[..., r:].reshape((L, n * slabs) + vp.shape[-2:]))
    return kp, vp


def _append_latent_lanes(kp: jax.Array, vp: jax.Array, pages: jax.Array,
                         offs: jax.Array, rows: jax.Array) -> Tuple[Any, ...]:
    """Write row ``m``'s latent row ``rows[:, m] (L, W)`` at ``(pages[m],
    offs[m])`` of a lane-layout pool: ``c`` by a token-row scatter, the
    rope key by reading its token's slab, putting the key in the token's
    place and writing the slab back.  A slot appends at most one token a
    step, so no two rows share a slab.  Padding rows' out-of-range pages
    read zeros and are dropped."""
    L, M, W = rows.shape
    r = kp.shape[-2] * kp.shape[-1]
    g = vp.shape[-2] * vp.shape[-1] // (W - r)       # tokens per slab
    kp = kp.at[:, pages, offs].set(
        rows[..., :r].reshape((L, M) + kp.shape[-2:]), mode="drop")
    slab = offs // g
    cur = vp.at[:, pages, slab].get(mode="fill", fill_value=0.0)
    mine = jnp.arange(g * (W - r)) // (W - r) == (offs % g)[:, None]
    new = jnp.where(mine, jnp.tile(rows[..., r:], g), cur.reshape(L, M, -1))
    vp = vp.at[:, pages, slab].set(new.reshape(cur.shape), mode="drop")
    return kp, vp


@functools.partial(jax.jit, donate_argnums=0)
def _write_prefill(pool: Tuple[Any, ...], pages: jax.Array, k: jax.Array,
                   v: Optional[jax.Array]) -> Tuple[Any, ...]:
    """Write a prefilled slot's dense cache ``k/v (L, 1, cap, Hk, hd)``
    into its ``cap // page`` pool ``pages`` in place (a latent pool: ``k``
    holds the rows ``(L, 1, cap, 1, W)``, ``v`` is None).  Past the
    prompt the dense cache holds zeros."""
    kp, vp = pool
    L, _, cap, Hk, hd = k.shape
    n = pages.shape[0]
    if v is None and vp is not None:
        return _write_latent_lanes(kp, vp, pages,
                                   k[:, 0, :, 0].astype(jnp.float32))
    kpg = k[:, 0].reshape(L, n, cap // n, Hk, hd).astype(jnp.float32)
    if vp is None:
        return kp.at[:, pages].set(kpg), None
    vpg = v[:, 0].reshape(L, n, cap // n, Hk, hd).astype(jnp.float32)
    return kp.at[:, pages].set(kpg), vp.at[:, pages].set(vpg)


@functools.partial(jax.jit, donate_argnums=0)
def _append_rows(pool: Tuple[Any, ...], pages: jax.Array, offs: jax.Array,
                 kn: Tuple[jax.Array, ...],
                 vn: Tuple[jax.Array, ...]) -> Tuple[Any, ...]:
    """Write row ``r``'s token k/v ``kn[r], vn[r] (L, Hk, hd)`` at
    ``(pages[r], offs[r])`` in place, for a fixed number of rows; padding
    rows carry an out-of-range page and are dropped.  A latent pool takes
    ``kn`` alone (``vn`` empty), split into its two arrays in the lane
    layout (``_append_latent_lanes``)."""
    kp, vp = pool
    k = jnp.stack(kn, axis=1)                       # (L, M, Hk, hd)
    if not vn and vp is not None:
        return _append_latent_lanes(kp, vp, pages, offs, k[:, :, 0])
    if vp is None:
        return kp.at[:, pages, offs].set(k, mode="drop"), None
    v = jnp.stack(vn, axis=1)
    return (kp.at[:, pages, offs].set(k, mode="drop"),
            vp.at[:, pages, offs].set(v, mode="drop"))


def _counts_experts(module: Any) -> bool:
    """Whether ``module`` holds a MoE layer told which experts it holds."""
    cfg = getattr(module, "cfg", None)
    if cfg is None or not cfg.experts_held:
        return False
    lo = getattr(module, "lo", 0)
    return any(cfg.is_moe_layer(lo + j) for j in range(module.n_layers))


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
                 "last_used", "length", "pages")

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


def _fused_block(cfg: Any, p: Any, x: jax.Array, positions: jax.Array,
                 bt: jax.Array, lengths: jax.Array, kp: jax.Array,
                 vp: Optional[jax.Array], layer: int,
                 rows_out: Optional[List[jax.Array]] = None,
                 ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """One attention-family block (global index ``layer``) for a batch of
    single-token rows, with KV read from the page pool.  Mirrors
    ``decoder.run_block``'s dense decode math exactly (rms_norm -> q/k/v
    -> qk_norm -> rope -> masked softmax over the cache -> wo -> residual
    -> ln2 -> mlp/moe).  Latent attention instead scores and sums the
    cached latent rows with ``W_UK`` absorbed into the query and ``W_UV``
    applied after (equal to ``mla.run_mla`` up to rounding), from one
    pool array of rows or, in the lane layout, from ``kp`` (latents) and
    ``vp`` (rope keys); its new row comes back as the "k" row, with no
    "v".  A MoE layer appends its rows per held expert, over the live
    rows, to ``rows_out``."""
    ap = p["attn"]
    B = x.shape[0]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla:
        row = mla.latent_rows(ap, cfg, h, positions)[:, 0]    # (B, W)
        o_lat = paged_latent_attention_jnp(
            mla.absorbed_query(ap, cfg, h[:, 0], positions),
            kp[:, :, 0] if vp is None else kp, bt, lengths, row,
            cfg.kv_lora_rank, mla.softmax_scale(cfg), rope_pool=vp)
        x = x + mla.absorbed_output(ap, cfg, o_lat)[:, None]
        k_row, v_row = row[:, None], None
    else:
        H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q = (h @ ap["wq"]).reshape(B, 1, H, hd)
        k = (h @ ap["wk"]).reshape(B, 1, Hk, hd)
        v = (h @ ap["wv"]).reshape(B, 1, Hk, hd)
        if cfg.qk_norm:
            q = rms_norm(q, ap["q_norm"], cfg.norm_eps)
            k = rms_norm(k, ap["k_norm"], cfg.norm_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        attn = paged_attention_jnp(q[:, 0], kp, vp, bt, lengths,
                                   k[:, 0], v[:, 0])             # (B, H, hd)
        x = x + attn.reshape(B, 1, H * hd) @ ap["wo"]
        k_row, v_row = k[:, 0], v[:, 0]
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.is_moe_layer(layer):
        ffn, _ = run_moe(p["moe"], cfg, h2, use_kernel=cfg.use_flash_kernel,
                         no_drop=True, rows_out=rows_out,
                         live=lengths > 0 if cfg.experts_held else None)
    else:
        ffn = run_mlp(p["mlp"], h2)
    return x + ffn, k_row, v_row


class BatchEngine:
    def __init__(self, module: Any, sim: Sim, n_slots: int = 8,
                 page_size: int = 32, fused: Optional[bool] = None):
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
        # A shard with MoE layers told which experts it holds also returns
        # their rows per held expert ``(layers, n_held)``.
        self._counts = _counts_experts(module)

        def dense_apply(params, x, pos, cache):
            m = _with_params(module, params)
            if not self._counts:
                return m.apply(x, pos, cache)
            rows: List[jax.Array] = []
            out, cache = m.apply(x, pos, cache, rows_out=rows)
            return out, cache, jnp.stack(rows)

        self._apply = jax.jit(dense_apply)
        supported = self._supports_fused(module)
        self.fused = supported if fused is None else (fused and supported)
        self._pool: Optional[KVPool] = None
        # this step's appends, written by one scatter after the step
        self._appends: List[Tuple[int, int, jax.Array, jax.Array]] = []
        self._fallback_pages = 0      # exact page counter for the dense path
        if self.fused:
            cfg = module.cfg
            dev = _device_of(module.params)
            if cfg.mla:
                self._pool = KVPool(module.n_layers, 1, cfg.latent_dim,
                                    page_size, device=dev, latent=True,
                                    rope_dim=cfg.qk_rope_head_dim)
            else:
                self._pool = KVPool(module.n_layers, cfg.n_kv_heads, cfg.hd,
                                    page_size, device=dev)
            self._fused_apply = jax.jit(self._build_fused_apply())
        self.stats = {
            "admitted": 0, "evicted": 0, "steps": 0,
            "step_sessions": 0, "queue_peak": 0, "slot_reuse": 0,
            "pages": 0, "pages_peak": 0, "idle_evicted": 0,
            # fused path: pool bytes written in place on the device (prefill
            # pages and appends), and the live rows' cached bytes per step
            "kv_bytes_written": 0, "kv_bytes_live": 0,
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
        count = self._counts

        def fused(params, x, positions, bt, lengths, kp, vp):
            m = _with_params(self.module, params)
            if m.is_first and x.dtype == jnp.int32:
                h = m.embed(x[:, None])                      # (M, 1, D)
            else:
                h = x[:, None, :]
            new_k: List[jax.Array] = []
            new_v: List[jax.Array] = []
            expert_rows: List[jax.Array] = []
            for j in range(m.n_layers):
                lp = m._layer_params(j)
                h, kn, vn = _fused_block(
                    cfg, lp, h, positions, bt, lengths, kp[j],
                    None if vp is None else vp[j], m.lo + j, expert_rows)
                new_k.append(kn)
                new_v.append(vn)
            out = m.head(h)[:, 0] if m.is_last else h[:, 0]
            # each row's k/v (L, Hk, hd) as an output of its own, so the
            # engine hands rows to the append without slicing on the host
            nk = jnp.stack(new_k)
            rows = range(nk.shape[1])
            nv = None if cfg.mla else jnp.stack(new_v)
            res = (out, tuple(nk[:, r] for r in rows),
                   () if nv is None else tuple(nv[:, r] for r in rows))
            # configs told which experts they hold also return each MoE
            # layer's rows per held expert; no other config's program changes
            return res + (jnp.stack(expert_rows),) if count else res

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
            return float(len(st.pages) * self._pool.page_bytes)
        if st.cache is None:
            return 0.0
        return float(sum(leaf.nbytes
                         for leaf in jax.tree.leaves(st.cache["layers"])))

    def kv_bytes(self) -> float:
        """Current cache-resident bytes across all live slots (pool pages,
        or dense per-slot caches)."""
        if self.fused:
            return float(self._pool.bytes_in_use())
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

    def touch(self, sessions: List[Any]) -> None:
        """Mark ``sessions`` used now: the service calls it as a reply
        leaves, so time spent queued behind this server's own work never
        counts as a client's idleness."""
        now = self.sim.now
        for sid in sessions:
            st = self.by_session.get(sid)
            if st is not None:
                st.last_used = now

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

    def _pool_write_prefill(self, st: SlotState, k: jax.Array,
                            v: jax.Array) -> int:
        """Write a prefilled slot's dense cache ``k/v (L, 1, cap, Hk, hd)``
        (latent pools: the rows ``(L, 1, cap, 1, latent_dim)`` and no v),
        as ``_apply`` left it on the device, into its pool pages in place.
        Returns the pool bytes written."""
        pool = self._pool
        pool.arrays = _write_prefill(pool.arrays,
                                     np.asarray(st.pages, np.int32), k, v)
        written = len(st.pages) * pool.page_bytes
        self.stats["kv_bytes_written"] += written
        return written

    def _pool_append(self, st: SlotState, kn: jax.Array,
                     vn: Optional[jax.Array]) -> None:
        """Append one token's k/v ``(L, Hk, hd)`` (or latent row, no v) at position
        ``st.length`` (the page was allocated before the fused call).
        The write is queued; ``_flush_appends`` makes the step's writes."""
        pos = st.length
        self._appends.append((st.pages[pos // self.page_size],
                              pos % self.page_size, kn, vn))
        st.length = pos + 1

    def _flush_appends(self) -> int:
        """Write the queued appends in place with one scatter of
        ``n_slots`` rows (padding rows are dropped), so it compiles once
        per pool size.  Returns the pool bytes written."""
        queued, self._appends = self._appends, []
        if not queued:
            return 0
        pool, M = self._pool, self.n_slots
        pages = np.full((M,), pool.n_pages, np.int32)
        offs = np.zeros((M,), np.int32)
        for r, (pid, off, _, _) in enumerate(queued):
            pages[r], offs[r] = pid, off
        pad = M - len(queued)
        kn = tuple(q[2] for q in queued) + (queued[0][2],) * pad
        vn = (() if pool.latent
              else tuple(q[3] for q in queued) + (queued[0][3],) * pad)
        pool.arrays = _append_rows(pool.arrays, pages, offs, kn, vn)
        written = len(queued) * pool.append_bytes
        self.stats["kv_bytes_written"] += written
        return written

    def _counted(self, span: Any, res: Tuple[Any, ...]) -> Tuple[Any, ...]:
        """The outputs of ``_apply`` or ``_fused_apply`` without the rows
        per held expert that a counting shard's programs add last; those
        go on ``span`` while the tracer is on (and only then come back to
        the host): ``expert_rows`` per MoE layer per held expert, and
        ``experts_hit``, the held experts with a row, per MoE layer."""
        if not self._counts:
            return res
        if tracing.TRACER.on:
            rows = np.asarray(res[-1])
            span.set(expert_rows=rows.tolist(),
                     experts_hit=(rows > 0).sum(axis=1).tolist())
        return res[:-1]

    def _prefill(self, session: Any, slot: int, x: np.ndarray,
                 max_len: int) -> Tuple[np.ndarray, float]:
        m = self.module
        with tracing.span("engine.prefill", flow=session, session=session,
                          tokens=int(x.shape[1])) as span:
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
                out, cache = self._counted(span, self._apply(
                    m.params, xj, self._positions(0, 1, S), cache))
                st.cache = None
                st.length = S
                st.pages = self._pool.alloc(cap // self.page_size)
                layers = cache["layers"]
                with tracing.span("kv.write_prefill",
                                  pages=len(st.pages)) as sp:
                    sp.set(bytes=self._pool_write_prefill(
                        st, *((layers["ckv"][..., None, :], None)
                              if m.cfg.mla else (layers["k"], layers["v"]))))
            else:
                self._fallback_pages += cap // self.page_size
                out, st.cache = self._counted(span, self._apply(
                    m.params, xj, self._positions(0, 1, S), st.cache))
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
        # the live rows' pages (allocated above, so appends do not change
        # them) are the part of the resident pool the step reads
        kv_read = sum(self._slot_kv_bytes(st) for _, _, st in live)
        self.stats["kv_bytes_live"] += int(kv_read)
        with tracing.span("engine.fused", rows=len(live)) as span:
            out, nk, nv = self._counted(span, self._fused_apply(
                m.params, jnp.asarray(xb), jnp.asarray(lengths[:, None]),
                jnp.asarray(bt), jnp.asarray(lengths), *pool.arrays))
            out = np.asarray(out)
        served: List[Any] = []
        with tracing.span("kv.append", rows=len(live)) as sp:
            for r, (_, sid, st) in enumerate(live):
                self._pool_append(st, nk[r], nv[r] if nv else None)
                served.append(sid)
            sp.set(bytes=self._flush_appends())
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
