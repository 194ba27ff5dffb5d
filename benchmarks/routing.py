"""The arithmetic of the routed form of the serving check
(`serve_cell.check_logits`): which experts a group-limited top-k router keeps,
and how far a program's choice falls short of the reference's. numpy, on the
host, over scores the family's plain reference has computed; nothing here
knows a model. A family whose router scores otherwise (a bias, another group
score) writes its own; the definition it has to keep is README.md's.

Experts are numbered as published; group j holds experts j * E/G .. (j+1) *
E/G - 1, and a group's score is its best expert's (`group_limited_greedy`).
Scores are positive (a softmax's), so ratios of them mean something.
"""

from __future__ import annotations

import numpy as np


def _top_mask(values: np.ndarray, count) -> np.ndarray:
    """True at the `count` largest of each row (ties: the lower index);
    `count` is a number or one number a row."""
    order = np.argsort(-values, axis=-1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(values.shape[-1])[None], axis=-1)
    return rank < np.reshape(count, (-1, 1))


def _kth_largest(values: np.ndarray, k: int) -> np.ndarray:
    return -np.sort(-values, axis=-1)[:, k - 1]


def reference_choice(scores, top_k: int, n_group: int = 1,
                     topk_group: int = 1) -> np.ndarray:
    """The experts a router keeps from `scores` (N, E): the `top_k` best
    inside the `topk_group` best of `n_group` groups. A mask (N, E)."""
    scores = np.asarray(scores, dtype=np.float64)
    n, e = scores.shape
    groups = _top_mask(scores.reshape(n, n_group, -1).max(-1), topk_group)
    inside = np.repeat(groups, e // n_group, axis=1)
    return _top_mask(np.where(inside, scores, -np.inf), top_k)


def shortfall(scores, kept, top_k: int, n_group: int = 1,
              topk_group: int = 1) -> np.ndarray:
    """How far the program's experts `kept` (N, top_k published ids) fall
    short of what the reference's `scores` (N, E) would have kept; 0 where
    the two sets are the same. The larger of
    (a) 1 - the score of the worst group that holds a program expert over the
        score of the last group the reference keeps (a router with groups);
    (b) 1 - the score of the program's worst expert over the k-th score
        inside the groups the program kept: its experts' groups, filled up to
        `topk_group` with the reference's best remaining."""
    scores = np.asarray(scores, dtype=np.float64)
    kept = np.asarray(kept)
    n, e = scores.shape
    per_group = e // n_group
    program = np.zeros((n, e), dtype=bool)
    np.put_along_axis(program, kept, True, axis=1)
    differ = (program != reference_choice(scores, top_k, n_group,
                                          topk_group)).any(-1)
    group_score = scores.reshape(n, n_group, per_group).max(-1)
    has_expert = program.reshape(n, n_group, per_group).any(-1)
    a = 1.0 - (np.where(has_expert, group_score, np.inf).min(-1)
               / _kth_largest(group_score, topk_group))
    groups = _top_mask(np.where(has_expert, np.inf, group_score),
                       np.maximum(topk_group, has_expert.sum(-1)))
    inside = np.repeat(groups, per_group, axis=1)
    b = 1.0 - (np.take_along_axis(scores, kept, axis=1).min(-1)
               / _kth_largest(np.where(inside, scores, -np.inf), top_k))
    return np.where(differ, np.maximum(np.maximum(a, b), 0.0), 0.0)
