"""Decides ``correct``: the served tokens against the plain reference.

After the window a sample of the finished requests, drawn from the seed
and always holding the longest one, is run through the reference once
per prompt with its served tokens.  Each served token's logit is read in
the reference's row at the position that predicted it: the number
compared is the widest gap, over every sampled token, by which it lies
below the row's best, in standard deviations of that row
(``worst_gap_sd``); and the served logits (which the client hands to its
per-token hook) against the reference's row: the root-mean-square
deviation (``worst_logit_rms_sd``) and the largest one
(``worst_logit_max_sd``), each the worst over the sample.  Greedy
decoding through a correct cache, position and weights keeps all of
them near 0; the served path's matmuls at the backend's default
precision move them a little; a wrong token, cache entry or weight
moves them by whole deviations.  ``mean_logit_rms_sd`` is the RMS
deviation's mean over the sample, a steadier reading of the same
thing.  A cell's limits file names the numbers it compares.

The control (``control=True``) is the reference in bfloat16 put in the
program's place: at the same positions of the same prompts and served
tokens, its logits and the token it puts first are measured the same way
and stand under the numbers' own names, so the same limits judge them;
the program's readings of that run are kept beside them as
``program_<number>``.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import record, traffic as tf


def sample(requests: List[record.Request], n: int,
           seed: int) -> List[record.Request]:
    """``n`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in requests if r.finished and len(r.tokens) == r.n_tokens]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: done[i].prompt_len + done[i].n_tokens)
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def _arrays(reqs: List[record.Request], mix: Dict[str, Any]):
    """Padded ``tokens (N, T)``, the predicting ``rows (N, R)``, the
    served tokens ``(N, R)`` and their logits ``(N, R, V)``, and the mask
    of real entries."""
    R = max(tf.output_lengths(mix))
    T = max(tf.prompt_lengths(mix)) + R
    N = len(reqs)
    V = reqs[0].logits[0].shape[-1]
    tokens = np.zeros((N, T), np.int32)
    rows = np.zeros((N, R), np.int32)
    served = np.zeros((N, R), np.int32)
    logits = np.zeros((N, R, V), np.float32)
    mask = np.zeros((N, R), bool)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        tokens[i, :len(seq)] = seq
        n = len(r.tokens)
        rows[i] = r.prompt_len - 1 + np.minimum(np.arange(R), n - 1)
        served[i] = [r.tokens[min(j, n - 1)] for j in range(R)]
        logits[i] = np.stack([r.logits[min(j, n - 1)] for j in range(R)])
        mask[i, :n] = True
    return tokens, rows, served, logits, mask


def compare(reqs: List[record.Request], config: Dict[str, Any],
            mix: Dict[str, Any], seed: int, family: Any,
            control: bool = False) -> Dict[str, Any]:
    """The served tokens and logits against the float32 reference, as the
    worst over the sample of each number (with ``control``, the control's
    under those names and the program's as ``program_<number>``); run
    once the deployment has been freed, since the reference makes the
    weights again."""
    tokens, rows, served, logits, mask = _arrays(reqs, mix)
    shards, batch = config["serving"]["shards"], mix["check_batch"]
    candidates = {"": (served, logits)}
    if control:
        ctrl = family.control_logits(config, seed, shards, tokens, rows, batch)
        candidates = {"program_": (served, logits),
                      "": (np.argmax(ctrl, axis=-1).astype(np.int32), ctrl)}
    got = family.reference_compare(config, seed, shards, tokens, rows,
                                   candidates, batch)
    out: Dict[str, Any] = {"tokens_checked": int(mask.sum()),
                           "requests_checked": len(reqs)}
    for pre, (gap, rms, top) in got.items():
        out[pre + "worst_gap_sd"] = float(np.max(gap[mask]))
        out[pre + "worst_logit_rms_sd"] = float(np.max(rms[mask]))
        out[pre + "worst_logit_max_sd"] = float(np.max(top[mask]))
        out[pre + "mean_logit_rms_sd"] = float(np.mean(rms[mask]))
        out[pre + "argmax_agree"] = float(np.mean(gap[mask] == 0.0))
    return out
