"""A llama-family config as the system under test takes it: its
``ModelConfig``.  Kept apart from ``llama.py`` so that the reference there
imports nothing of the system."""

from __future__ import annotations

from typing import Any, Dict

#: config keys the system has no setting for; the cell runs them at the
#: value that leaves the equations unchanged, and says so in ``reduced``
_IDENTITY = {"scale_emb": lambda c: 1.0,
             "scale_depth": lambda c: c["num_hidden_layers"] ** 0.5,
             "dim_model_base": lambda c: c["hidden_size"]}


def program_config(c: Dict[str, Any]) -> Any:
    from repro.models.config import ModelConfig

    for key, ident in _IDENTITY.items():
        if key in c and abs(c[key] - ident(c)) > 1e-9 * abs(ident(c)):
            raise ValueError(f"{c['name']}: the system cannot run "
                             f"{key}={c[key]} (only {ident(c)})")
    if c.get("hidden_act", "silu") != "silu" or c.get("attention_bias") \
            or c.get("mlp_bias"):
        raise ValueError(f"{c['name']}: the system runs SwiGLU blocks "
                         "without biases only")
    return ModelConfig(
        name=c["name"], arch="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c.get("head_dim") or 0,
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=bool(c.get("tie_word_embeddings")))
