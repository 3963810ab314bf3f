"""Speculative decoding, host half: the self-drafting n-gram cache.

Draft-then-verify (Leviathan et al. 2023) needs a cheap proposer; this
one is prompt-lookup decoding — no drafter model, no extra weights. Per
slot it keeps the request's full token history (prompt + generated) and
proposes the continuation of the most recent PRIOR occurrence of the
current n-gram suffix, backing off n → n-1 → ... → 1 and falling back
to repeat-last-token when nothing matches (cheap, and exactly right in
the repetition regimes greedy decode falls into — which is also where
speculation pays most). The device half
(``ServedModel.verify`` + ``served.spec_accept``) writes the k
drafts through the block table in ONE batched verify step and accepts
the longest agreeing prefix, so greedy output stays bit-identical to
non-speculative decode whatever this proposer suggests — a bad draft
costs compute, never correctness.

All host work is list slicing over small histories: zero device syncs,
zero compiled-shape variance (k is static).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


class NGramDrafter:
    """Per-slot n-gram proposer over the request token histories."""

    def __init__(self, k: int, ngram: int = 3, max_history: int = 4096):
        if k < 1:
            raise ValueError(f"spec_k must be >= 1 to draft, got {k}")
        self.k = int(k)
        self.ngram = max(1, int(ngram))
        self.max_history = int(max_history)
        self._history: Dict[int, List[int]] = {}
        # Cumulative proposer stats (how often the n-gram cache had a
        # real match vs the repeat-last fallback) — the acceptance rate
        # itself is measured at verify time by the engine.
        self.lookups = 0
        self.matches = 0

    # ---- history lifecycle (engine-driven) ---- #
    def begin(self, slot: int, prompt: Sequence[int]) -> None:
        self._history[slot] = [int(t) for t in prompt]

    def observe(self, slot: int, tokens: Sequence[int]) -> None:
        h = self._history.setdefault(slot, [])
        h.extend(int(t) for t in tokens)
        if len(h) > self.max_history:
            del h[:len(h) - self.max_history]

    def reset(self, slot: int) -> None:
        self._history.pop(slot, None)

    # ---- proposal ---- #
    def propose(self, slot: int) -> np.ndarray:
        """k draft tokens continuing the slot's history. Always returns
        a full-k array (the verify step is one fixed shape); the
        repeat-last fallback fills whatever the n-gram cache can't."""
        h = self._history.get(slot) or [0]
        self.lookups += 1
        draft: List[int] = []
        for n in range(min(self.ngram, len(h) - 1), 0, -1):
            suffix = h[-n:]
            # Most recent prior occurrence: scan right-to-left over the
            # history, excluding the suffix occurrence itself.
            for i in range(len(h) - n - 1, -1, -1):
                if h[i:i + n] == suffix:
                    cont = h[i + n:i + n + self.k]
                    if cont:
                        draft = cont
                        break
            if draft:
                self.matches += 1
                break
        while len(draft) < self.k:
            draft.append(draft[-1] if draft else h[-1])
        return np.asarray(draft[:self.k], np.int32)

    def match_rate(self) -> float:
        return self.matches / self.lookups if self.lookups else 0.0


__all__ = ["NGramDrafter"]
