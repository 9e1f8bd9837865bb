"""A DeepSeek-V2-family config as the system under test takes it: its
``ModelConfig``.  Kept apart from ``deepseek_v2.py`` so that the reference
there imports nothing of the system."""

from __future__ import annotations

from typing import Any, Dict

#: config keys the system runs at one value only; any other raises
_ONLY = {"q_lora_rank": None, "topk_method": "greedy", "n_group": 1,
         "topk_group": 1, "scoring_func": "softmax",
         "routed_scaling_factor": 1, "moe_layer_freq": 1,
         "hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False}


def program_config(c: Dict[str, Any]) -> Any:
    from repro.models.config import ModelConfig

    for key, only in _ONLY.items():
        if c.get(key, only) != only:
            raise ValueError(f"{c['name']}: the system cannot run "
                             f"{key}={c[key]!r} (only {only!r})")
    rs = c.get("rope_scaling") or {}
    if rs and (rs.get("type") != "yarn"
               or rs.get("mscale") != rs.get("mscale_all_dim")):
        raise ValueError(f"{c['name']}: the system runs YaRN rope scaling "
                         f"with mscale == mscale_all_dim only, not {rs}")
    cut = c.get("reduced", {}).get("n_routed_experts")
    return ModelConfig(
        name=c["name"], arch="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_attention_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"],
        # the router scores every published expert; this chip holds the
        # first ``n_routed_experts`` of them
        n_experts=cut[0] if cut else c["n_routed_experts"],
        experts_held=tuple(range(c["n_routed_experts"])),
        n_shared_experts=c["n_shared_experts"],
        moe_top_k=c["num_experts_per_tok"],
        d_expert=c["moe_intermediate_size"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        first_dense_layers=c["first_k_dense_replace"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        rope_factor=float(rs.get("factor", 1.0)),
        rope_orig_max_pos=int(rs.get("original_max_position_embeddings", 0)),
        yarn_beta_fast=float(rs.get("beta_fast", 32.0)),
        yarn_beta_slow=float(rs.get("beta_slow", 1.0)),
        yarn_mscale=float(rs.get("mscale_all_dim", 1.0)))
