"""Llama-family decoder: the benchmark's weight maker, plain reference and
operation and byte counts.

Imports nothing of the system under test.  The weights are the
benchmark's input: made from the seed on the device, in one jitted call,
in the tree layout the system's pipeline shards read::

    {"blocks": {"ln1", "attn": {"wq", "wk", "wv", "wo"}, "ln2",
                "mlp": {"w_gate", "w_up", "w_down"}},    # stacked by layer
     "embed"       (first shard),
     "final_norm", "lm_head" or tied "embed_out"  (last shard)}

The reference is the published block written out in ``jax.numpy``:
pre-norm RMSNorm, rotary position embedding on the two halves of each
head, causal grouped-query softmax attention, SwiGLU feed-forward, final
RMSNorm and the output head.  The muP multipliers of the config
(``scale_emb``, ``scale_depth``, ``dim_model_base``) are applied when the
config names them.  No cache, no batching of sessions, no kernels; float32
at ``highest`` matmul precision unless a control asks for less.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ------------------------------------------------------------------ shapes
def dims(c: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the weight maker and the reference read from a config."""
    D = c["hidden_size"]
    H = c["num_attention_heads"]
    hd = c.get("head_dim") or D // H
    return {"D": D, "H": H, "Hk": c["num_key_value_heads"], "hd": hd,
            "F": c["intermediate_size"], "V": c["vocab_size"],
            "L": c["num_hidden_layers"]}


def plan(n_layers: int, n_shards: int) -> List[Tuple[int, int]]:
    """Contiguous layer ranges, as even as possible, earlier shards first."""
    base, rem = divmod(n_layers, n_shards)
    out, lo = [], 0
    for i in range(n_shards):
        hi = lo + base + (1 if i < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, beyond 32 bits too."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


# ------------------------------------------------------------------- counts
# Operations and bytes from the config's shapes: what the work needs, not
# what the program happens to compute (padded rows and slots count
# nothing).  A multiply-add is 2 operations.  ``prefill_flops``,
# ``decode_flops`` and ``fused_step`` are what the harness reads of every
# family.
_BYTES = {"float32": 4, "bfloat16": 2}


def layer_params(c: Dict[str, Any]) -> int:
    """Weights of one block: q, k, v, o projections, SwiGLU, two norms."""
    d = dims(c)
    D, H, Hk, hd, F = d["D"], d["H"], d["Hk"], d["hd"], d["F"]
    return 2 * D * H * hd + 2 * D * Hk * hd + 3 * D * F + 2 * D


def head_flops(c: Dict[str, Any]) -> int:
    d = dims(c)
    return 2 * d["D"] * d["V"]


def attention_flops(c: Dict[str, Any], n_layers: int, pos: int) -> int:
    """Scores and weighted values of one query at position ``pos`` (which
    attends ``pos + 1`` keys) through ``n_layers`` layers."""
    d = dims(c)
    return 4 * n_layers * d["H"] * d["hd"] * (pos + 1)


def token_flops(c: Dict[str, Any], pos: int, n_layers: int = 0) -> int:
    """One token at position ``pos`` through the blocks (no head)."""
    L = n_layers or c["num_hidden_layers"]
    return 2 * L * layer_params(c) + attention_flops(c, L, pos)


def prefill_flops(c: Dict[str, Any], prompt_len: int) -> int:
    """A prompt through the whole model, and the head once at its end."""
    d = dims(c)
    L = d["L"]
    return (prompt_len * 2 * L * layer_params(c)
            + 4 * L * d["H"] * d["hd"] * prompt_len * (prompt_len + 1) // 2
            + head_flops(c))


def decode_flops(c: Dict[str, Any], pos: int) -> int:
    """The token fed at position ``pos``, through the whole model and the
    head: the operations behind one served token after the first."""
    return token_flops(c, pos) + head_flops(c)


def kv_bytes_per_token(c: Dict[str, Any], n_layers: int) -> int:
    d = dims(c)
    return 2 * n_layers * d["Hk"] * d["hd"] * _BYTES[c["serving"]["kv_dtype"]]


def fused_step(c: Dict[str, Any], n_layers: int, first: bool, last: bool,
               lengths: Sequence[int], param_bytes: int) -> Dict[str, int]:
    """Operations and HBM bytes of one shard's batched decode step over
    the live rows with ``lengths`` cached tokens each: every weight of the
    shard's blocks read once, the head once if last, the embedding rows if
    first, each row's cached keys and values read and its new ones
    written; ``param_bytes`` is the served weights' bytes per number."""
    d = dims(c)
    D, V, pb = d["D"], d["V"], param_bytes
    rows = len(lengths)
    flops = sum(token_flops(c, n, n_layers) for n in lengths)
    weights = n_layers * layer_params(c) * pb
    if last:
        flops += rows * head_flops(c)
        weights += (D * V + D) * pb
    if first:
        weights += rows * D * pb
    kv = kv_bytes_per_token(c, n_layers) * (sum(lengths) + rows)
    return {"flops": flops, "bytes": weights + kv}


# ------------------------------------------------------------------ weights
def _leaf(key: jax.Array, shape: Tuple[int, ...], std: float,
          dtype: Any) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer(c: Dict[str, Any], key: jax.Array, dtype: Any) -> Params:
    d = dims(c)
    D, H, Hk, hd, F = d["D"], d["H"], d["Hk"], d["hd"], d["F"]
    k = jax.random.split(key, 7)
    ones = jnp.ones((D,), dtype)
    return {"ln1": ones, "ln2": ones,
            "attn": {"wq": _leaf(k[0], (D, H * hd), D ** -0.5, dtype),
                     "wk": _leaf(k[1], (D, Hk * hd), D ** -0.5, dtype),
                     "wv": _leaf(k[2], (D, Hk * hd), D ** -0.5, dtype),
                     "wo": _leaf(k[3], (H * hd, D), (H * hd) ** -0.5, dtype)},
            "mlp": {"w_gate": _leaf(k[4], (D, F), D ** -0.5, dtype),
                    "w_up": _leaf(k[5], (D, F), D ** -0.5, dtype),
                    "w_down": _leaf(k[6], (F, D), F ** -0.5, dtype)}}


def _frozen(c: Dict[str, Any]) -> Tuple:
    """The config's numbers as a hashable static argument, so that every
    call with the same config and shapes reuses one compiled program."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _build(key: jax.Array, fc: Tuple, layer_plan: Tuple, dtype: Any):
    c = dict(fc)
    d = dims(c)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    shards = []
    for lo, hi in layer_plan:
        keys = jnp.stack([jax.random.fold_in(k_layers, l)
                          for l in range(lo, hi)])
        shards.append({"blocks": jax.vmap(
            lambda k: _layer(c, k, dtype))(keys)})
    shards[-1]["final_norm"] = jnp.ones((d["D"],), dtype)
    embed = _leaf(k_embed, (d["V"], d["D"]), 0.02, dtype)
    head = (None if c.get("tie_word_embeddings")
            else _leaf(k_head, (d["D"], d["V"]), d["D"] ** -0.5, dtype))
    return shards, embed, head


def make_shards(c: Dict[str, Any], seed: int,
                layer_plan: Sequence[Tuple[int, int]],
                dtype: Any = jnp.float32) -> List[Params]:
    """Every shard's weights from ``seed``, in one jitted call on the
    default device.  Layer ``l`` always comes from the same key, so the
    values do not depend on the plan; a tied embedding exists once and is
    shared by the first and the last shard."""
    shards, embed, head = _build(seed_key(seed), _frozen(c),
                                 tuple(map(tuple, layer_plan)),
                                 jnp.dtype(dtype))
    shards[0]["embed"] = embed
    if c.get("tie_word_embeddings"):
        shards[-1]["embed_out"] = embed
    else:
        shards[-1]["lm_head"] = head
    return shards


# ---------------------------------------------------------------- reference
def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (B, S, heads, hd): rotate the two halves of each head by the
    angle ``position * theta ** (-2i / hd)``."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv        # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _block(c: Dict[str, Any], p: Params, x: jax.Array) -> jax.Array:
    d = dims(c)
    B, S, _ = x.shape
    H, Hk, hd = d["H"], d["Hk"], d["hd"]
    eps = c["rms_norm_eps"]
    branch = c.get("scale_depth", math.sqrt(d["L"])) / math.sqrt(d["L"])
    h = _rms(x, p["ln1"], eps)
    a = p["attn"]
    q = _rope((h @ a["wq"]).reshape(B, S, H, hd), c["rope_theta"])
    k = _rope((h @ a["wk"]).reshape(B, S, Hk, hd), c["rope_theta"])
    v = (h @ a["wv"]).reshape(B, S, Hk, hd)
    q = q.reshape(B, S, Hk, H // Hk, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k).astype(jnp.float32)
    s = s / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqt,btkd->bqkgd", w, v).reshape(B, S, H * hd)
    x = x + (o @ a["wo"]) * branch
    h = _rms(x, p["ln2"], eps)
    m = p["mlp"]
    ffn = (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
    return x + ffn * branch


def _stage(c: Dict[str, Any], p: Params, x: jax.Array) -> jax.Array:
    def body(x, bp):
        return _block(c, bp, x), None
    x, _ = jax.lax.scan(body, x, p["blocks"])
    return x


def _logits(c: Dict[str, Any], p: Params, x: jax.Array) -> jax.Array:
    x = _rms(x, p["final_norm"], c["rms_norm_eps"])
    w = p["lm_head"] if "lm_head" in p else p["embed_out"].T
    scale = c["hidden_size"] / c.get("dim_model_base", c["hidden_size"])
    return (x @ w).astype(jnp.float32) / scale


def _forward_rows(c: Dict[str, Any], shards: List[Params], tokens: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """Logits ``(B, R, V)`` at sequence positions ``rows (B, R)``."""
    x = jnp.take(shards[0]["embed"], tokens, axis=0)
    x = x * jnp.asarray(c.get("scale_emb", 1.0), x.dtype)
    for p in shards:
        x = _stage(c, p, x)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)
    return _logits(c, shards[-1], x)


@functools.partial(jax.jit, static_argnums=(0,))
def _compare(fc: Tuple, shards: List[Params], tokens: jax.Array,
             rows: jax.Array, scored: jax.Array,
             logits: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """At each position, in standard deviations of the reference's own
    row: the gap by which the scored token's logit lies below the row's
    best, and the root-mean-square and the largest deviation of
    ``logits`` from the row."""
    with jax.default_matmul_precision("highest"):
        z = _forward_rows(dict(fc), shards, tokens, rows)
    sd = z.std(-1)
    picked = jnp.take_along_axis(z, scored[..., None], axis=-1)[..., 0]
    d = logits - z
    return ((z.max(-1) - picked) / sd,
            jnp.sqrt(jnp.mean(d * d, axis=-1)) / sd,
            jnp.max(jnp.abs(d), axis=-1) / sd)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_at(fc: Tuple, shards: List[Params], tokens: jax.Array,
               rows: jax.Array) -> jax.Array:
    return _forward_rows(dict(fc), shards, tokens, rows).astype(jnp.float32)


def _blocks(n: int, size: int):
    for lo in range(0, n, size):
        yield lo, lo + size


def _pad(a: np.ndarray, size: int) -> np.ndarray:
    """Rows repeated up to a multiple of ``size``: every block one shape."""
    extra = -len(a) % size
    return np.concatenate([a, np.repeat(a[:1], extra, axis=0)]) if extra else a


def reference_compare(c: Dict[str, Any], seed: int, n_shards: int,
                      tokens: np.ndarray, rows: np.ndarray,
                      candidates: Dict[str, Tuple[np.ndarray, np.ndarray]],
                      batch: int) -> Dict[str, Tuple[np.ndarray, ...]]:
    """Float32 reference at ``highest`` precision over ``tokens (N, T)``,
    read at positions ``rows (N, R)``, against each candidate's ``(tokens
    (N, R), logits (N, R, V))``: per position its token's gap and its
    logits' RMS and largest deviation (see ``_compare``).  The weights are
    made again from the seed; sequences go through in blocks of
    ``batch``."""
    shards = make_shards(c, seed, plan(c["num_hidden_layers"], n_shards))
    n, fc = len(tokens), _frozen(c)
    tokens, rows = _pad(tokens, batch), _pad(rows, batch)
    out = {}
    for name, (scored, logits) in candidates.items():
        scored, logits = _pad(scored, batch), _pad(logits, batch)
        parts = [_compare(fc, shards, jnp.asarray(tokens[lo:hi]),
                          jnp.asarray(rows[lo:hi]), jnp.asarray(scored[lo:hi]),
                          jnp.asarray(logits[lo:hi]))
                 for lo, hi in _blocks(len(tokens), batch)]
        out[name] = tuple(np.concatenate([np.asarray(p[i]) for p in parts])[:n]
                          for i in range(3))
    del shards
    return out


def control_logits(c: Dict[str, Any], seed: int, n_shards: int,
                   tokens: np.ndarray, rows: np.ndarray,
                   batch: int) -> np.ndarray:
    """The control: the reference computed in bfloat16 (weights and
    activations; norms and softmax statistics in float32), read at the
    same positions of the same prompts and served tokens."""
    shards = make_shards(c, seed, plan(c["num_hidden_layers"], n_shards),
                         dtype=jnp.bfloat16)
    n, fc = len(tokens), _frozen(c)
    tokens, rows = _pad(tokens, batch), _pad(rows, batch)
    out = [np.asarray(_logits_at(fc, shards, jnp.asarray(tokens[lo:hi]),
                                 jnp.asarray(rows[lo:hi])))
           for lo, hi in _blocks(len(tokens), batch)]
    del shards
    return np.concatenate(out)[:n]
