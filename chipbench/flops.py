"""Operations and bytes from a config's shapes: what the work needs, not
what the program happens to compute (padded rows and slots count
nothing).  A multiply-add is 2 operations."""

from __future__ import annotations

from typing import Any, Dict, Sequence

_BYTES = {"float32": 4, "bfloat16": 2}


def _d(c: Dict[str, Any]):
    D, H = c["hidden_size"], c["num_attention_heads"]
    hd = c.get("head_dim") or D // H
    return D, H, c["num_key_value_heads"], hd, c["intermediate_size"], c["vocab_size"]


def layer_params(c: Dict[str, Any]) -> int:
    """Weights of one block: q, k, v, o projections, SwiGLU, two norms."""
    D, H, Hk, hd, F, _ = _d(c)
    return 2 * D * H * hd + 2 * D * Hk * hd + 3 * D * F + 2 * D


def head_flops(c: Dict[str, Any]) -> int:
    D, *_, V = _d(c)
    return 2 * D * V


def attention_flops(c: Dict[str, Any], n_layers: int, pos: int) -> int:
    """Scores and weighted values of one query at position ``pos`` (which
    attends ``pos + 1`` keys) through ``n_layers`` layers."""
    _, H, _, hd, _, _ = _d(c)
    return 4 * n_layers * H * hd * (pos + 1)


def token_flops(c: Dict[str, Any], pos: int, n_layers: int = 0) -> int:
    """One token at position ``pos`` through the blocks (no head)."""
    L = n_layers or c["num_hidden_layers"]
    return 2 * L * layer_params(c) + attention_flops(c, L, pos)


def prefill_flops(c: Dict[str, Any], prompt_len: int) -> int:
    """A prompt through the whole model, and the head once at its end."""
    L = c["num_hidden_layers"]
    return (prompt_len * 2 * L * layer_params(c)
            + 4 * L * _d(c)[1] * _d(c)[3] * prompt_len * (prompt_len + 1) // 2
            + head_flops(c))


def decode_flops(c: Dict[str, Any], pos: int) -> int:
    """The token fed at position ``pos``, through the whole model and the
    head: the operations behind one served token after the first."""
    return token_flops(c, pos) + head_flops(c)


def kv_bytes_per_token(c: Dict[str, Any], n_layers: int) -> int:
    _, _, Hk, hd, _, _ = _d(c)
    return 2 * n_layers * Hk * hd * _BYTES[c["serving"]["kv_dtype"]]


def fused_step(c: Dict[str, Any], n_layers: int, first: bool, last: bool,
               lengths: Sequence[int], param_bytes: int) -> Dict[str, int]:
    """Operations and HBM bytes of one shard's batched decode step over
    the live rows with ``lengths`` cached tokens each: every weight of the
    shard's blocks read once, the head once if last, the embedding rows if
    first, each row's cached keys and values read and its new ones
    written; ``param_bytes`` is the served weights' bytes per number."""
    D, *_, V = _d(c)
    pb = param_bytes
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
