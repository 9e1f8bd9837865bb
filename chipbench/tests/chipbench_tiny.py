"""A tiny cell for the CPU tests: the llama family at toy widths under a
toy chat mix, and the entries a BENCHMARK.json needs for it."""

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {
    "name": "tiny", "family": "llama", "num_hidden_layers": 2,
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 2048,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "serving": {"shards": 2, "page_size": 8, "kv_dtype": "float32"}}
MIX = {"name": "tinychat", "clients": 3, "n_slots": 3,
       "prompt_tokens": [[8, 3]], "output_tokens": [[4, 1], [8, 1], [12, 1]],
       "check_requests": 16, "check_batch": 8}
#: on the CPU the served path computes in float32 throughout, so sound
#: runs read a gap of 0; the bfloat16 control read 0.0067 to 0.048 on
#: seeds 11 to 13 (about 130 tokens each)
LIMIT = 0.002
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def cell():
    return {"name": "tiny.tinychat", "chips": 1, "config": copy.deepcopy(CONFIG),
            "traffic": copy.deepcopy(MIX),
            "limits": {"worst_gap_sd": {"limit": LIMIT},
                       "worst_logit_rms_sd": {"limit": LIMIT}}}


def bench():
    """The repository's BENCHMARK.json with the tiny cell added to every
    metric that lists its cells."""
    from chipbench import spec

    b = copy.deepcopy(spec.load_benchmark())
    b["configs"].append({"name": "tiny", "source": "test", "file": "-",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny.tinychat", "config": "tiny",
                           "traffic": "tinychat", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.tinychat")
    return b
