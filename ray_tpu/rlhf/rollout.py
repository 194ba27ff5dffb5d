"""RLHF rollout plane: seq-numbered experiences off the serving engine.

Rollout generation runs on `LLMEngine` — continuous batching, paged KV,
and the prefix cache warm across the shared system prompt (every rollout
prompt is `system_prompt + prompt`, so after the first prefill the system
prompt's full blocks are cache hits for the rest of the round).

Integrity is the design center, not throughput: every prompt gets a
monotonic sequence number at admission and the `RolloutCoordinator` is
the single ledger of issued/completed work. Replica death mid-batch
re-queues the incomplete seq_nos; a straggling duplicate completion is
dropped and counted. The end state the RLHF smoke counter-proves —
"no experience lost or duplicated across a placement switch or a killed
generator" — is an assertion over this ledger.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class Experience:
    """One completed rollout: the unit the learner consumes."""
    seq_no: int
    prompt: List[int]            # WITHOUT the system prompt
    response: List[int]
    reward: float
    weights_version: int         # params version the tokens were sampled under
    replica: str = ""            # generator that produced it (chaos forensics)


def default_reward(prompt: Sequence[int], response: Sequence[int]) -> float:
    """Synthetic stand-in reward: distinct-token fraction of the response
    (favors non-repetitive continuations). Deterministic, picklable, and
    cheap — real deployments pass a reward-model callable instead."""
    if not response:
        return 0.0
    return len(set(response)) / len(response)


class RolloutCoordinator:
    """Driver-side ledger of rollout work: pending -> issued -> done.

    Exactly-once completion: `complete()` drops (and counts) any seq_no
    already done — a replica that answered after being declared dead, or a
    retried batch overlapping its original, cannot double-feed the
    learner. `requeue()` moves issued work back to the FRONT of pending so
    recovered prompts keep their position roughly in order.
    """

    def __init__(self):
        self._next_seq = 0
        self._pending: deque = deque()            # (seq_no, prompt)
        self._issued: Dict[int, List[int]] = {}   # seq_no -> prompt
        self._done: Dict[int, Experience] = {}
        self.dup_completions = 0
        self.requeues = 0

    def add_prompts(self, prompts: Sequence[Sequence[int]]) -> List[int]:
        seqs = []
        for p in prompts:
            self._pending.append((self._next_seq, list(p)))
            seqs.append(self._next_seq)
            self._next_seq += 1
        return seqs

    def take(self, n: int) -> List[Tuple[int, List[int]]]:
        """Hand out up to n pending prompts, marking them issued."""
        out = []
        while self._pending and len(out) < n:
            seq, prompt = self._pending.popleft()
            self._issued[seq] = prompt
            out.append((seq, prompt))
        return out

    def complete(self, experiences: Sequence[Experience]) -> List[Experience]:
        """Record completions; returns the ones that were NEW."""
        fresh = []
        for exp in experiences:
            if exp.seq_no in self._done:
                self.dup_completions += 1
                continue
            self._done[exp.seq_no] = exp
            self._issued.pop(exp.seq_no, None)
            fresh.append(exp)
        return fresh

    def requeue(self, seq_nos: Sequence[int]) -> int:
        """Return issued-but-incomplete prompts to the front of pending
        (generator death / drain during a placement switch)."""
        n = 0
        for seq in sorted(seq_nos, reverse=True):
            prompt = self._issued.pop(seq, None)
            if prompt is None or seq in self._done:
                continue
            self._pending.appendleft((seq, prompt))
            n += 1
        self.requeues += n
        return n

    def requeue_all_issued(self) -> int:
        return self.requeue(list(self._issued))

    @property
    def pending_count(self) -> int:
        return len(self._pending)

    @property
    def issued_count(self) -> int:
        return len(self._issued)

    def round_complete(self) -> bool:
        return not self._pending and not self._issued

    def drain_done(self) -> List[Experience]:
        """Pop all completed experiences in seq_no order."""
        out = [self._done[s] for s in sorted(self._done)]
        self._done.clear()
        return out

    def ledger(self) -> dict:
        return {"next_seq": self._next_seq,
                "pending": self.pending_count,
                "issued": self.issued_count,
                "dup_completions": self.dup_completions,
                "requeues": self.requeues}


def rollout_seed(base_seed: int, seq_no: int) -> int:
    """Per-prompt sampling seed: a function of (base_seed, seq_no) ONLY, so
    a re-queued prompt regenerates the identical tokens on any replica and
    batching order never leaks into the sampled stream."""
    return (base_seed * 1_000_003 + seq_no) & 0x7FFFFFFF


def run_rollout_round(engine, items: Sequence[Tuple[int, Sequence[int]]], *,
                      system_prompt: Sequence[int] = (),
                      max_new_tokens: int = 16,
                      temperature: float = 0.0,
                      base_seed: int = 0,
                      reward_fn: Optional[Callable] = None,
                      replica: str = "") -> List[Experience]:
    """Generate one batch of rollouts on `engine` (continuous batching:
    all items admitted up front, the engine interleaves their prefill and
    decode). Returns one Experience per item."""
    from ray_tpu.llm.sampling import SamplingParams

    reward_fn = reward_fn or default_reward
    sys_p = list(system_prompt)
    params = [SamplingParams(temperature=temperature,
                             max_tokens=max_new_tokens,
                             seed=rollout_seed(base_seed, seq))
              for seq, _ in items]
    rid_to_item = {}
    for (seq, prompt), sp in zip(items, params):
        rid = engine.add_request(sys_p + list(prompt), sp)
        rid_to_item[rid] = (seq, list(prompt))
    done: Dict[str, List[int]] = {}
    while engine.has_unfinished():
        for out in engine.step():
            if out.finished and out.request_id in rid_to_item:
                done[out.request_id] = list(out.output_token_ids)
    version = getattr(engine, "weights_version", 0)
    exps = []
    for rid, (seq, prompt) in rid_to_item.items():
        response = done.get(rid, [])
        exps.append(Experience(
            seq_no=seq, prompt=prompt, response=response,
            reward=float(reward_fn(prompt, response)),
            weights_version=version, replica=replica))
    return exps


class RolloutReplica:
    """Actor-hostable generator: a tiny llama `LLMEngine` plus the RLHF
    weight-sync entry points. Decorate with `ray_tpu.remote` at the use
    site (the `_QueueActor` pattern) or drive in-process for colocated
    mode and benchmarks."""

    def __init__(self, model_kwargs: dict, rollout_kwargs: dict = None, *,
                 num_kv_blocks: int = 128,
                 block_size: int = 8, max_batch_size: int = 4,
                 init_seed: int = 0, name: str = "gen0",
                 weight_refs=None, weight_meta=None,
                 weights_version: int = 0):
        import jax
        import jax.numpy as jnp

        from ray_tpu import models
        from ray_tpu.llm.engine import LLMEngine
        from ray_tpu.llm.model_runner import ModelRunner
        from ray_tpu.models import llama
        from ray_tpu.rlhf import weight_sync

        kwargs = dict(model_kwargs)
        kwargs.setdefault("dtype", jnp.float32)
        self.config = llama.LlamaConfig.tiny(**kwargs)
        self.name = name
        # Rollout parameters are construction-time state, not per-call RPC
        # payload (the reward callable would otherwise re-pickle per round).
        self.rollout_kwargs = dict(rollout_kwargs or {})
        if weight_refs is not None:
            params = weight_sync.assemble_weights(weight_refs, weight_meta)
        else:
            params = models.draw_params(self.config,
                                        jax.random.key(init_seed))
        runner = ModelRunner(self.config, params, num_blocks=num_kv_blocks,
                             block_size=block_size)
        self.engine = LLMEngine(runner, max_batch_size=max_batch_size)
        self.engine.weights_version = weights_version

    def generate(self, items):
        return run_rollout_round(self.engine, items, replica=self.name,
                                 **self.rollout_kwargs)

    def sync_weights(self, refs, meta, version: int) -> int:
        """Disaggregated weight sync: read the broadcast leaves zero-copy
        from the local store and hot-swap them into the engine."""
        from ray_tpu.rlhf import weight_sync

        params = weight_sync.assemble_weights(refs, meta)
        return self.engine.update_weights(params, version=version)["version"]

    def engine_stats(self) -> dict:
        return self.engine.stats()

    def lm_leaves(self, meta):
        """Engine-resident weights as numpy leaves (meta order) — the
        generator half of the weight-sync bit-identity assertion."""
        import numpy as np

        from ray_tpu.rlhf import weight_sync

        return [np.asarray(l) for l in
                weight_sync.flatten_weights(self.engine.runner.params, meta)]

    def greedy_tokens(self, prompt, max_new_tokens: int = 8):
        """Bit-identity probe: greedy continuation under current weights."""
        from ray_tpu.llm.sampling import SamplingParams

        out = self.engine.generate(
            [list(prompt)], SamplingParams(max_tokens=max_new_tokens))[0]
        return list(out.output_token_ids)

    def ping(self) -> str:
        return self.name
