"""The llama family's operation and byte counts (``models/llama.py``)
against hand counts for both configurations."""

import os

import pytest

import chipbench_tiny
from chipbench import spec

llama, _ = spec.family({"family": "llama"})


def _cfg(name):
    return spec.load_json(os.path.join(chipbench_tiny.ROOT, "chipbench",
                                       "configs", name + ".json"))


def test_minicpm_hand_counts():
    c = _cfg("minicpm-2b")
    # q, k, v, o: 4 x 2304 x 2304; SwiGLU: 3 x 2304 x 5760; 2 norms
    layer = 4 * 2304 * 2304 + 3 * 2304 * 5760 + 2 * 2304
    assert llama.layer_params(c) == layer == 61_051_392
    assert 40 * layer + 122753 * 2304 + 2304 == c["params"]
    assert llama.kv_bytes_per_token(c, 40) == 40 * 2 * 36 * 64 * 4 == c["kv_bytes_per_token"]
    # a token at position 99: 2 x 40 layers' weights, 36 heads of 64 over 100 keys
    assert llama.token_flops(c, 99) == 2 * 40 * layer + 4 * 40 * 36 * 64 * 100
    assert llama.decode_flops(c, 99) == llama.token_flops(c, 99) + 2 * 2304 * 122753


def test_granite_hand_counts():
    c = _cfg("granite-8b-9l")
    # q, o: 4096 x 4096; k, v: 4096 x (8 x 128); SwiGLU 3 x 4096 x 14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert llama.layer_params(c) == layer == 218_112_000
    assert 9 * layer + 2 * 49152 * 4096 + 4096 == c["params"]
    assert llama.kv_bytes_per_token(c, 9) == 9 * 2 * 8 * 128 * 4 == c["kv_bytes_per_token"]
    # prefill of 4 tokens: positions 0..3 attend 1..4 keys, head once
    want = 4 * 2 * 9 * layer + 4 * 9 * 32 * 128 * (1 + 2 + 3 + 4) + 2 * 4096 * 49152
    assert llama.prefill_flops(c, 4) == want
    assert llama.prefill_flops(c, 4) == sum(
        llama.token_flops(c, p) for p in range(4)) + llama.head_flops(c)


def test_fused_step_floor_of_the_last_granite_shard():
    c = _cfg("granite-8b-9l")
    layer = llama.layer_params(c)
    got = llama.fused_step(c, 4, first=False, last=True, lengths=[10, 30],
                            param_bytes=4)
    assert got["flops"] == (2 * 4 * layer * 2 + 4 * 4 * 32 * 128 * (11 + 31)
                            + 2 * (2 * 4096 * 49152))
    kv_token = 2 * 4 * 8 * 128 * 4
    assert got["bytes"] == (4 * layer * 4 + (4096 * 49152 + 4096) * 4
                            + kv_token * (10 + 30 + 2))


def test_fused_step_floor_of_the_first_minicpm_shard():
    c = _cfg("minicpm-2b")
    got = llama.fused_step(c, 20, first=True, last=False, lengths=[64] * 8,
                            param_bytes=4)
    kv_token = 2 * 20 * 36 * 64 * 4
    assert got["bytes"] == (20 * llama.layer_params(c) * 4 + 8 * 2304 * 4
                            + kv_token * (8 * 64 + 8))
    assert got["flops"] == 8 * llama.token_flops(c, 64, 20)
    assert got["flops"] == pytest.approx(8 * (2 * 20 * 61_051_392
                                              + 4 * 20 * 36 * 64 * 65))
