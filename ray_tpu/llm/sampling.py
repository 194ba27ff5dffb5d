"""Token sampling."""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 0.0          # 0 = greedy
    top_k: int = 0                    # 0 = disabled
    top_p: float = 1.0
    max_tokens: int = 64
    stop_token_ids: Optional[List[int]] = None
    repetition_penalty: float = 1.0
    seed: Optional[int] = None


def sample(logits: np.ndarray, params: SamplingParams,
           prev_tokens: Optional[np.ndarray] = None) -> int:
    logits = np.asarray(logits, dtype=np.float64).copy()
    if params.repetition_penalty != 1.0 and prev_tokens is not None \
            and prev_tokens.size:
        seen = np.unique(prev_tokens)
        pos = logits[seen] > 0
        logits[seen[pos]] /= params.repetition_penalty
        logits[seen[~pos]] *= params.repetition_penalty
    if params.temperature <= 0.0:
        return int(np.argmax(logits))
    probs = _softmax(nucleus(logits, params))
    rng = np.random.default_rng(params.seed)
    return int(rng.choice(len(probs), p=probs))


def nucleus(logits: np.ndarray, params: SamplingParams) -> np.ndarray:
    """Temperature, top-k and top-p as the device's filter applies them
    (`ModelRunner._filter_logits`), -inf where a token is dropped: the k-th
    largest and its ties stay; of their softmax, a token stays while the mass
    strictly above it is under `top_p`, so the crossing token and every tie
    with a kept token are in (the top token always is)."""
    logits = np.asarray(logits, dtype=np.float64) / max(params.temperature,
                                                        1e-6)
    if params.top_k > 0:
        k = min(params.top_k, logits.size)
        logits[logits < np.partition(logits, -k)[-k]] = -np.inf
    if params.top_p < 1.0:
        probs = _softmax(logits)
        sp = np.sort(probs)[::-1]
        inside = np.cumsum(sp) - sp < params.top_p
        inside[0] = True
        logits[probs < sp[inside].min()] = -np.inf
    return logits


def _softmax(x: np.ndarray) -> np.ndarray:
    x = x - np.max(x[np.isfinite(x)] if np.isfinite(x).any() else x)
    e = np.exp(np.where(np.isfinite(x), x, -np.inf))
    return e / e.sum()
