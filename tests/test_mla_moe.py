"""Latent attention (MLA), YaRN and the expert share on a tiny
DeepSeek-shaped config: the shares of every expert group add up to the
uncut layer, absorbed paged decode equals the dense form, the serving
plane matches the benchmark's plain reference, prefill drops no token,
and YaRN's numbers are the published formulas'."""

import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.simnet import Sim
from repro.kernels.paged_attention import paged_latent_attention_jnp
from repro.models import decoder, mla
from repro.models.config import ModelConfig
from repro.models.moe import expert_share, init_moe, run_moe
from repro.serving.batch import BatchEngine
from repro.serving.sharded import ShardModule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: d 64, 4 heads, latent 32, rope 8, nope 16, v 16; 8 experts of 32, top 2,
#: one shared; one dense layer of 96 first
CFG = ModelConfig(
    name="ds-tiny", arch="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, vocab=256, n_experts=8, n_shared_experts=1,
    moe_top_k=2, d_expert=32, norm_topk_prob=False, first_dense_layers=1,
    experts_held=(0, 1), kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0, rope_factor=40.0,
    rope_orig_max_pos=64, yarn_mscale=0.707, norm_eps=1e-6)


def _normal(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape) * scale


# ----------------------------------------------------------- (a) the share
def _uncut_layer(p, x, k):
    """Every expert, token by token, in float64: softmax over all experts,
    the top k without renormalisation, plus the shared expert."""
    x = np.asarray(x, np.float64)
    w = {n: np.asarray(a, np.float64) for n, a in p.items() if n != "shared"}
    sh = {n: np.asarray(a, np.float64) for n, a in p["shared"].items()}

    def silu(z):
        return z / (1 + np.exp(-z))

    out = np.zeros_like(x)
    for t in range(len(x)):
        z = x[t] @ w["router"]
        prob = np.exp(z - z.max())
        prob /= prob.sum()
        for e in np.argsort(-prob)[:k]:
            h = silu(x[t] @ w["w_gate"][e]) * (x[t] @ w["w_up"][e])
            out[t] += prob[e] * (h @ w["w_down"][e])
        out[t] += (silu(x[t] @ sh["w_gate"]) * (x[t] @ sh["w_up"])) @ sh["w_down"]
    return out


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four devices of 2 experts each: their routed parts, with the shared
    expert (which every device computes alike) counted once, give the
    layer with all 8 experts."""
    whole = dataclasses.replace(CFG, experts_held=tuple(range(8)))
    p = init_moe(whole, jax.random.PRNGKey(3), jnp.float32)
    x = _normal(4, (24, 64))
    total = jnp.zeros_like(x)
    for g in range(4):
        held = (2 * g, 2 * g + 1)
        part = {n: p[n][2 * g:2 * g + 2] for n in ("w_gate", "w_up", "w_down")}
        share = jax.jit(functools.partial(
            expert_share, cfg=dataclasses.replace(CFG, experts_held=held)))
        y, hits = share(dict(part, router=p["router"]), xt=x)
        total = total + y
        assert hits.shape == (24, 2)
    total = total + (jax.nn.silu(x @ p["shared"]["w_gate"])
                     * (x @ p["shared"]["w_up"])) @ p["shared"]["w_down"]
    want = _uncut_layer(p, x, 2)
    np.testing.assert_allclose(np.asarray(total), want, rtol=2e-5, atol=2e-6)
    y, _ = jax.jit(lambda p: run_moe(p, whole, x[None], no_drop=True))(p)
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=2e-5, atol=2e-6)


# ------------------------------------------------- (b) absorbed == dense
def test_absorbed_paged_decode_equals_the_dense_form():
    p = mla.init_mla(CFG, jax.random.PRNGKey(5), jnp.float32)
    lengths = np.array([5, 11, 1], np.int32)       # cached tokens per slot
    page, n_pages = 4, 16
    S = int(lengths.max()) + 1
    h = _normal(6, (3, S, 64))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (3, S))
    rows = jax.jit(lambda p: mla.latent_rows(p, CFG, h, pos))(p)  # (3, S, 40)
    # scatter each slot's cached rows into pool pages of a shuffled table
    order = np.random.default_rng(7).permutation(n_pages)
    bt = order[:12].reshape(3, 4).astype(np.int32)
    pool = np.zeros((n_pages, page, CFG.latent_dim), np.float32)
    cached = np.asarray(rows)
    for m, n in enumerate(lengths):
        for t in range(n):
            pool[bt[m, t // page], t % page] = cached[m, t]
    idx = jnp.asarray(lengths)

    @jax.jit
    def absorbed(p, pool):
        hq = h[jnp.arange(3), idx]                   # each slot's new token
        q = mla.absorbed_query(p, CFG, hq, idx[:, None])
        o = paged_latent_attention_jnp(q, pool, jnp.asarray(bt), idx,
                                       rows[jnp.arange(3), idx],
                                       CFG.kv_lora_rank, mla.softmax_scale(CFG))
        return mla.absorbed_output(p, CFG, o)

    got = absorbed(p, jnp.asarray(pool))
    dense, _ = jax.jit(lambda p: mla.run_mla(p, CFG, h, pos))(p)  # per head
    want = dense[jnp.arange(3), idx]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# --------------------------------------- (c) serving plane == reference
TINY = {
    "name": "tinyds", "family": "deepseek_v2", "num_hidden_layers": 3,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "first_k_dense_replace": 1,
    "norm_topk_prob": False, "routed_scaling_factor": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "greedy", "scoring_func": "softmax",
    "hidden_act": "silu", "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64},
    "reduced": {"n_routed_experts": [8, 2]},
    "serving": {"shards": 2, "page_size": 4, "kv_dtype": "float32"}}


@pytest.fixture(scope="module")
def family():
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import spec
    return spec.family({"family": "deepseek_v2"})


def test_batch_engine_on_two_shards_matches_the_reference(family):
    ref, program = family
    cfg = program.program_config(TINY)
    plan = ref.plan(3, 2)
    parts = ref.make_shards(TINY, 9, plan)
    sim = Sim(seed=1)
    engs = [BatchEngine(ShardModule(cfg, parts[i], plan[i], i == 0, i == 1),
                        sim, n_slots=2, page_size=4) for i in range(2)]
    assert all(e.fused for e in engs)
    assert engs[0]._pool.vp is None and engs[0]._pool.kp.shape[-2:] == (1, 40)
    prompt = (np.arange(6, dtype=np.int32) * 37 % 256)[None]
    x = prompt
    for e in engs:
        x, _ = sim.run_process(e.open("A", x, 16))
    seq, logits = list(prompt[0]), [x[0]]
    for _ in range(5):                               # greedy, through pages
        tok = np.asarray([int(np.argmax(logits[-1]))], np.int32)
        seq.append(int(tok[0]))
        y = tok
        for e in engs:
            y, served, _ = e.step(["A"], y)
        logits.append(y[0])
    # each served row against the reference's row at the position that
    # predicted it (positions 5..10 of prompt and served tokens)
    scored = np.asarray([[int(np.argmax(z)) for z in logits]], np.int32)
    gap, rms, top = ref.reference_compare(
        TINY, 9, 2, np.asarray([seq], np.int32),
        np.arange(5, 11, dtype=np.int32)[None],
        {"": (scored, np.asarray(logits)[None])}, 1)[""]
    assert np.all(gap == 0.0)
    assert float(np.max(top)) < 1e-4
    # the last shard's one layer: 2 prefill pages of 4 rows, 5 appended
    # rows, each row one latent of 40 float32 numbers
    assert engs[1].stats["kv_bytes_written"] == 2 * 4 * 40 * 4 + 5 * 40 * 4


# ------------------------------------------------ (d) prefill drops nothing
def test_prefill_with_every_token_on_one_expert_drops_nothing():
    """600 tokens (past the 512 at which the capacity dispatch caps an
    expert at twice the mean load), every one routed to held expert 0:
    each gets its full share, and the count says 600."""
    p = init_moe(CFG, jax.random.PRNGKey(11), jnp.float32)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(1.0)
    x = jnp.abs(_normal(12, (1, 600, 64))) + 0.1     # logit of expert 0 > 0
    rows = []
    y, _ = run_moe(p, CFG, x, no_drop=True, rows_out=rows)
    assert rows[0].tolist()[0] == 600
    full = dict(p, router=p["router"],
                w_gate=jnp.zeros((8, 64, 32)).at[:2].set(p["w_gate"]),
                w_up=jnp.zeros((8, 64, 32)).at[:2].set(p["w_up"]),
                w_down=jnp.zeros((8, 32, 64)).at[:2].set(p["w_down"]))
    want = _uncut_layer(full, x[0], 2)
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=2e-5,
                               atol=2e-6 * np.abs(want).max())


# ------------------------------------------------------------- (e) YaRN
def test_yarn_frequencies_scale_and_rope_layout_by_hand():
    c = dataclasses.replace(CFG, qk_nope_head_dim=128, qk_rope_head_dim=64,
                            rope_orig_max_pos=4096)

    def corr(rot):       # yarn_find_correction_dim, dim 64, base 10000
        return 64 * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(1e4))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    inv = np.asarray(mla.rope_inv_freq(c))
    for i in (0, 10, 16, 23, 31):
        extra = 1 / 1e4 ** (2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = extra / 40 * ramp + extra * (1 - ramp)
        assert inv[i] == pytest.approx(want, rel=1e-6), i
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.26080, abs=5e-6)
    assert mla.softmax_scale(c) == pytest.approx(192 ** -0.5 * mscale ** 2,
                                                 rel=1e-12)
    # the published layout: pair (2i, 2i+1) rotates into halves i and 32+i
    x = np.zeros((1, 1, 64), np.float32)
    x[..., 2], x[..., 3] = 1.0, 2.0
    out = np.asarray(mla.apply_rope_interleaved(
        jnp.asarray(x), jnp.full((1, 1), 7), mla.rope_inv_freq(c)))[0, 0]
    a = 7 * inv[1]
    assert out[1] == pytest.approx(math.cos(a) - 2 * math.sin(a), abs=1e-5)
    assert out[33] == pytest.approx(2 * math.cos(a) + math.sin(a), abs=1e-5)
    assert np.count_nonzero(out) == 2


def test_param_counts_know_latent_attention_and_held_experts():
    D = 64
    attn = D * 4 * 24 + D * 40 + 32 + 32 * 4 * 32 + 4 * 16 * D
    moe = attn + 2 * D + D * 8 + 2 * 3 * D * 32 + 3 * D * 32
    dense = attn + 2 * D + 3 * D * 96
    assert CFG.param_count() == dense + 2 * moe + 2 * 256 * D + D
    params = decoder.init_params(CFG, jax.random.PRNGKey(0))
    assert CFG.param_count() == sum(a.size for a in jax.tree.leaves(params))
    # a token reaches 2 of 8 experts, so 1/4 of each held one on average
    assert CFG.active_param_count() == CFG.param_count() - 2 * 2 * 3 * D * 32 \
        + 2 * 3 * D * 32 * 2 * 2 // 8
