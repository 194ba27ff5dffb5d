"""The one general traffic generator. A traffic mix is a data file under
`traffic/`; this reads its parameters and makes the requests from `--seed`.

Every seed gets the SAME requests in shape and order (a grid of `pool`
quantiles of each distribution, shuffled once and for all) and the same gaps,
with other token ids (and other weights): so two seeds do the same work, and
runs differ by the system.

Serving mixes (`kind` "closed" or "open"):

    prompt_len / output_len   {"median", "sigma", "min", "max"}: lognormal,
                              clipped; output_len is `max_tokens`, no stop id
    shared_prefixes           optional {"count", "len", "zipf_s"}: a request's
                              prompt is one of `count` shared prefixes (picked
                              Zipf) followed by `prompt_len` unshared tokens
    sampled_every / sampled   every n-th request samples with these settings
                              and a seed of its own; the rest are greedy
    pool, max_requests        size of the quantile grid; requests made
    warm_s                    seconds of this traffic sent before the window
    closed: clients, ramp_s   clients, and the seconds their starts spread over
    open:   rate_per_s        Poisson arrivals at this fixed rate

Training mixes (`kind` "train_steps"): `global_batch`, `seq`, `warm_steps`;
the tokens are drawn on the device, a new batch each step (see train_cell).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


def quantile_grid(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def lognormal_lengths(spec: Dict, n: int) -> List[int]:
    """n lengths at the grid quantiles of a clipped lognormal."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    z = NormalDist()
    return [int(min(spec["max"], max(spec["min"], round(
        math.exp(mu + sigma * z.inv_cdf(q)))))) for q in quantile_grid(n)]


def exponential_gaps(rate_per_s: float, n: int) -> List[float]:
    """n inter-arrival gaps at the grid quantiles of Exp(rate), rescaled so
    that their mean is exactly 1/rate."""
    raw = [-math.log(1.0 - q) for q in quantile_grid(n)]
    scale = n / (rate_per_s * sum(raw))
    return [g * scale for g in raw]


def zipf_picks(count: int, s: float, n: int) -> List[int]:
    """n picks of 0..count-1 in Zipf(s) proportions (largest remainder)."""
    w = [1.0 / (k + 1) ** s for k in range(count)]
    share = [x * n / sum(w) for x in w]
    picks = [int(x) for x in share]
    by_rest = sorted(range(count), key=lambda k: share[k] - picks[k],
                     reverse=True)
    for k in by_rest[:n - sum(picks)]:
        picks[k] += 1
    return [k for k in range(count) for _ in range(picks[k])]


def _tokens(seed: int, stream: int, n: int, vocab: int) -> List[int]:
    return np.random.default_rng([seed, stream]).integers(
        1, vocab, n).tolist()


def make_requests(traffic: Dict, seed: int, vocab: int) -> Dict:
    """{"requests": [...], "prefixes": [...]}: request k is a dict for
    `LLMServer.completions_stream` plus `due_s`, its arrival offset from the
    generator's start (open loop; None in a closed loop). `prefixes` are the
    shared prefixes, each to be served once in set-up so its pages are cached.
    """
    pool, n = int(traffic["pool"]), int(traffic["max_requests"])
    # The pool's requests, their order and the gaps are fixed, the same for
    # every seed: a request's work is prompt x output, and which requests meet
    # in a batch follows from their order. The seed gives the token ids (and
    # the weights). With a free order per seed, two seeds' tokens/s differed
    # by 5%, with a fixed cycle entered at a seeded point by 2.6%, where two
    # runs of one seed differ by 0.5% (my chip runs, PR 25).
    fixed = np.random.default_rng(pool)

    def shuffled(values):
        return [values[i] for i in fixed.permutation(len(values))]

    shared = traffic.get("shared_prefixes")
    prompt_lens = shuffled(lognormal_lengths(traffic["prompt_len"], pool))
    output_lens = shuffled(lognormal_lengths(traffic["output_len"], pool))
    picks = (shuffled(zipf_picks(shared["count"], shared["zipf_s"], pool))
             if shared else [None] * pool)
    prefixes = ([_tokens(seed, 1_000_000 + p, shared["len"], vocab)
                 for p in range(shared["count"])] if shared else [])
    shapes = list(zip(prompt_lens, output_lens, picks))
    gaps: Optional[List[float]] = None
    if traffic["kind"] == "open":
        gaps = shuffled(exponential_gaps(traffic["rate_per_s"], pool))
    every = int(traffic.get("sampled_every", 0))
    requests, due = [], 0.0
    for k in range(n):
        prompt_len, output_len, pick = shapes[k % pool]
        prompt = _tokens(seed, 1 + k, prompt_len, vocab)
        if pick is not None:
            prompt = prefixes[pick] + prompt
        req = {"prompt": prompt, "max_tokens": output_len,
               "request_id": f"s{seed}-{k}", "due_s": None}
        if every and k % every == every - 1:
            req.update(traffic["sampled"], seed=(seed + k) % (2**31 - 1))
        if gaps is not None:
            due += gaps[k % pool]
            req["due_s"] = due
        requests.append(req)
    return {"requests": requests, "prefixes": prefixes}
