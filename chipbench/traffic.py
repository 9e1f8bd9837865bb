"""The one traffic generator: reads a mix's parameters (a file under
``traffic/``) and yields requests from the seed.

A mix gives its prompt and answer lengths as ``[[tokens, count], ...]``:
the counts of each list add up to the same block size.  Requests come in
blocks of that size; each block holds every prompt length and every
answer length as often as its count says, paired in an order drawn from
the seed.  So every seed sends the same prompt and answer tokens in every
block, in another order and with other token ids: a seed changes which
request comes when, not how much work there is.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Deque, Dict, List, Tuple

import numpy as np

Shape = Tuple[int, int]


def _expand(pairs: List[List[int]]) -> List[int]:
    return [int(n) for n, k in pairs for _ in range(int(k))]


def block(mix: Dict[str, Any]) -> Tuple[List[int], List[int]]:
    """One block's prompt lengths and answer lengths, in file order."""
    prompts = _expand(mix["prompt_tokens"])
    outputs = _expand(mix["output_tokens"])
    if len(prompts) != len(outputs) or not prompts:
        raise ValueError(f"mix {mix.get('name')!r}: prompt and answer counts "
                         f"add up to {len(prompts)} and {len(outputs)}")
    return prompts, outputs


def prompt_lengths(mix: Dict[str, Any]) -> List[int]:
    return sorted(set(block(mix)[0]))


def output_lengths(mix: Dict[str, Any]) -> List[int]:
    return sorted(set(block(mix)[1]))


def request_shapes(mix: Dict[str, Any]) -> List[Shape]:
    """Every (prompt tokens, answer tokens) pair a block can hold."""
    return [(s, n) for s in prompt_lengths(mix) for n in output_lengths(mix)]


def pages_for(n_tokens: int, page: int) -> int:
    return max(1, -(-n_tokens // page))


def session_pages(shape: Shape, page: int) -> List[int]:
    """Pages a session of this shape holds at each of its decode steps:
    the step that feeds the token at position ``l`` needs ``l + 1`` slots."""
    s, n = shape
    return [pages_for(l + 1, page) for l in range(s, s + n - 1)]


def max_session_pages(mix: Dict[str, Any], page: int) -> int:
    return max([pages_for(s + 1, page) for s, _ in request_shapes(mix)]
               + [max(session_pages(sh, page), default=1)
                  for sh in request_shapes(mix)])


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def warmup_shapes(mix: Dict[str, Any], page: int) -> List[Shape]:
    """Requests that, run one at a time, reach every prompt length and
    every block-table width (pages padded to a power of two) that the mix
    can reach, each as short as that allows."""
    reach = {_pow2(p) for sh in request_shapes(mix)
             for p in session_pages(sh, page)}
    longest = max(output_lengths(mix))
    seen: set = set()
    out: List[Shape] = []
    for s in prompt_lengths(mix):
        widths = [_pow2(p) for p in session_pages((s, longest), page)]
        need = 2                      # one decode step at least
        for i, w in enumerate(widths):
            if w not in seen:
                seen.add(w)
                need = i + 2
        out.append((s, need))
    missing = reach - seen
    if missing:
        raise ValueError(f"warm-up cannot reach block-table widths {missing}")
    return out


class Traffic:
    """Requests of one mix for one seed, in an endless seeded order."""

    def __init__(self, mix: Dict[str, Any], vocab: int, seed: int):
        self.prompts, self.outputs = block(mix)
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, 0x7AFF1C])
        self._queue: Deque[Tuple[np.ndarray, int]] = deque()

    def next(self) -> Tuple[np.ndarray, int]:
        """``(prompt ids (S,) int32, answer tokens)``."""
        if not self._queue:
            ps = self.rng.permutation(self.prompts)
            ns = self.rng.permutation(self.outputs)
            for s, n in zip(ps, ns):
                ids = self.rng.integers(0, self.vocab, int(s)).astype(np.int32)
                self._queue.append((ids, int(n)))
        return self._queue.popleft()
