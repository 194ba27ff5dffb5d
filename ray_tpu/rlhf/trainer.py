"""RLHF pipeline: serving-engine rollouts + Train learners, adaptively placed.

The trainer wires four existing planes into one loop:

  rollout (llm/)      PPO update (train/ + rl/ppo)      weight sync
  ----------------    ---------------------------       -----------------
  LLMEngine rounds -> util.queue -> QueueLearnerLoop  -> colocated: device
  (continuous          -> LearnerWorker gang             channel hot-swap
   batching, prefix       (TCP collective,            -> disaggregated:
   cache warm on the      bucketed allreduce)            object-plane put +
   shared system                                         fanout broadcast
   prompt)

Placement is a runtime decision, not a config constant: a
`PlacementPolicy` reads the telemetry plane's rollout-vs-update phase
breakdown and the engine's KV occupancy each iteration and can switch
the pipeline between

  * colocated     — generator runs in the driver process, time-slicing
    the slice with the learner gang; weight sync is an in-place hot-swap
    through a DeviceChannel (raw dlpack bytes, no pickle);
  * disaggregated — generator replicas are dedicated actors; weight sync
    is rank 0 publishing leaves into the object plane and fanning them
    out through `util/broadcast.py`'s raylet relay tree.

A switch drains in-flight work (rollouts re-queued by seq_no, the
learner loop drained through its STOP barrier), captures the full
learner state (policy + optimizer leaves), tears both gangs down, and
re-forms them under a FRESH collective group name — the same
re-formation discipline as the Train controller's gang restart, which is
what makes the switch safe mid-run. Every switch emits a typed
`RLHF_PLACEMENT_SWITCH` cluster event.

Integrity is counter-proven, not assumed: every prompt carries a
monotonic seq_no from the `RolloutCoordinator` ledger, the learner loop
records every seq_no it consumed, and the e2e smoke asserts the two
sets match exactly across switches and generator failures.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ray_tpu.rlhf.placement import (
    COLOCATED,
    DISAGGREGATED,
    MODES,
    PlacementPolicy,
)
from ray_tpu.rlhf.rollout import (
    Experience,
    RolloutCoordinator,
    RolloutReplica,
    default_reward,
)

ADAPTIVE = "adaptive"


def default_prompt_fn(index: int, length: int, vocab: int) -> List[int]:
    """Deterministic synthetic prompt stream (tokens in [1, vocab))."""
    return [1 + (3 + 7 * index + 11 * j) % (vocab - 1) for j in range(length)]


@dataclasses.dataclass
class RLHFConfig:
    """Everything the RLHF loop needs; defaults sized for the CPU mesh."""
    # model / generation
    model_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    system_prompt: Tuple[int, ...] = (2, 3, 5, 7)
    iterations: int = 2
    prompts_per_iter: int = 4
    prompt_len: int = 6
    max_new_tokens: int = 8
    temperature: float = 0.0
    seed: int = 0
    # PPO hyperparameters
    lr: float = 1e-3
    clip_eps: float = 0.2
    kl_coef: float = 0.05
    gamma: float = 0.99
    lam: float = 0.95
    vf_coef: float = 0.5
    ent_coef: float = 0.0
    ppo_epochs: int = 1
    # placement
    placement_mode: str = ADAPTIVE          # colocated|disaggregated|adaptive
    initial_mode: str = COLOCATED
    placement_policy: Optional[PlacementPolicy] = None
    force_switch_at: Optional[int] = None   # switch AFTER this iteration idx
    # gangs
    learner_world: int = 1
    num_generators: int = 1
    num_kv_blocks: int = 128
    block_size: int = 8
    max_batch_size: int = 4
    learner_options: Dict[str, Any] = dataclasses.field(default_factory=dict)
    generator_options: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # used when the generator gang is rebuilt after a failure (chaos tests
    # point this at surviving nodes)
    generator_fallback_options: Optional[Dict[str, Any]] = None
    # plumbing
    reward_fn: Optional[Callable] = None
    prompt_fn: Optional[Callable[[int], List[int]]] = None
    # Streaming prompt source: a ray_tpu.data Dataset whose rows carry
    # token lists in `prompt_column`. Pulled through the pipelined data
    # plane (iter_batches(prefetch_batches=...)) and cycled at epoch end,
    # so prompt transform/read cost overlaps rollouts instead of stalling
    # each iteration. Falls back to prompt_fn when unset.
    prompt_dataset: Optional[Any] = None
    prompt_column: str = "tokens"
    run_name: str = "rlhf"
    rollout_get_timeout: float = 120.0
    update_wait_timeout: float = 300.0
    # When set, every placement switch also persists the (policy, opt)
    # state to this directory via the async checkpoint plane — durability
    # for the drain-and-reform hand-off without lengthening the switch.
    state_checkpoint_dir: Optional[str] = None
    max_generator_rebuilds: int = 3


class LearnerWorker:
    """One PPO learner rank. Hosts the policy (llama LM + scalar value
    head), the reference LM for KL shaping, and the optimizer state;
    gradient averaging goes through the Train backend's bucketed
    `allreduce_gradients` on an explicitly named TCP collective group.

    Collective rendezvous happens in `setup()` — NOT `__init__` — so the
    gang's ranks can rendezvous concurrently (the test_collective idiom).
    Decorate with `ray_tpu.remote` at the use site.
    """

    def __init__(self, rank: int, world: int, model_kwargs: dict,
                 hyper: dict, seed: int, init_leaves=None,
                 start_version: int = 0):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu import models
        from ray_tpu.models import llama
        from ray_tpu.rl import ppo
        from ray_tpu.rlhf import weight_sync

        self.rank = int(rank)
        self.world = int(world)
        self.hyper = dict(hyper)
        self.group_name: Optional[str] = None
        self.version = int(start_version)
        self._ckpt_plane = None  # lazy ray_tpu.checkpoint.CheckpointPlane

        kwargs = dict(model_kwargs)
        kwargs.setdefault("dtype", jnp.float32)
        self.config = llama.LlamaConfig.tiny(**kwargs)

        # Deterministic seed init on every rank (identical params without a
        # broadcast); the reference LM is frozen at this init so KL is
        # measured against the same anchor before and after any placement
        # switch (state restore below does not touch it).
        lm = models.draw_params(self.config, jax.random.key(seed))
        self.ref_lm = lm
        d = self.config.d_model
        policy = {"lm": lm,
                  "vf": {"w": jnp.zeros((d, 1), jnp.float32),
                         "b": jnp.zeros((1,), jnp.float32)}}
        self.optimizer = optax.adam(self.hyper["lr"])
        opt_state = self.optimizer.init(policy)
        if init_leaves is not None:
            # Placement-switch restore: the fresh gang rebuilds the SAME
            # template locally and adopts the captured leaves, so only raw
            # arrays ever cross the wire — never a pickled treedef.
            treedef = jax.tree_util.tree_structure((policy, opt_state))
            policy, opt_state = jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(l) for l in init_leaves])
        self.policy = policy
        self.opt_state = opt_state
        self.lm_meta = weight_sync.describe_weights(self.policy["lm"])

        cfgm = self.config
        hp = self.hyper
        clip = hp["clip_eps"]

        def _logits_values(policy, tokens):
            hidden = llama.backbone(policy["lm"], tokens, cfgm)
            h32 = hidden.astype(jnp.float32)
            logits = h32 @ policy["lm"]["lm_head"].astype(jnp.float32)
            values = (h32 @ policy["vf"]["w"])[..., 0] + policy["vf"]["b"]
            return logits, values

        def _stats(policy, ref_lm, tokens, resp_mask, rewards, valid):
            # Behavior logprobs (stop-grad snapshot for the PPO ratio),
            # KL-shaped per-token rewards, GAE advantages/returns.
            logits, values = _logits_values(policy, tokens)
            logp = ppo.token_logprobs(logits[:, :-1], tokens[:, 1:])
            ref_logits = llama.forward(ref_lm, tokens, cfgm)
            ref_logp = ppo.token_logprobs(ref_logits[:, :-1], tokens[:, 1:])
            m = resp_mask[:, 1:] * valid[:, None]
            kl = ppo.kl_from_logprobs(logp, ref_logp) * m
            term = m * (1.0 - jnp.concatenate(
                [m[:, 1:], jnp.zeros_like(m[:, :1])], axis=1))
            r = -hp["kl_coef"] * kl + rewards[:, None] * term
            v = values[:, :-1] * m
            adv_t, ret_t = ppo.compute_gae(
                r.T, v.T, term.T, jnp.zeros_like(rewards),
                hp["gamma"], hp["lam"])
            adv, ret = adv_t.T, ret_t.T
            mean = ppo.masked_mean(adv, m)
            var = ppo.masked_mean((adv - mean) ** 2, m)
            adv = (adv - mean) / jnp.sqrt(var + 1e-8)
            return logp, adv * m, ret, m, ppo.masked_mean(kl, m)

        def _loss(policy, tokens, old_logp, adv, ret, m):
            logits, values = _logits_values(policy, tokens)
            logp = ppo.token_logprobs(logits[:, :-1], tokens[:, 1:])
            ratio = jnp.exp(logp - old_logp)
            clipped = jnp.clip(ratio, 1.0 - clip, 1.0 + clip)
            pg = -ppo.masked_mean(jnp.minimum(ratio * adv, clipped * adv), m)
            vloss = ppo.masked_mean((values[:, :-1] - ret) ** 2, m)
            logp_all = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            ent = ppo.masked_mean(
                -(jnp.exp(logp_all) * logp_all).sum(-1), m)
            total = pg + hp["vf_coef"] * vloss - hp["ent_coef"] * ent
            return total, (pg, vloss, ent)

        def _apply(grads, opt_state, policy):
            updates, new_opt = self.optimizer.update(
                grads, opt_state, policy)
            return optax.apply_updates(policy, updates), new_opt

        self._stats_fn = jax.jit(_stats)
        self._grad_fn = jax.jit(jax.value_and_grad(_loss, has_aux=True))
        self._apply_fn = jax.jit(_apply)

    # -- gang lifecycle -----------------------------------------------------
    def setup(self, group_name: str) -> int:
        if self.world > 1:
            from ray_tpu.collective.collective import init_collective_group

            init_collective_group(self.world, self.rank, backend="tcp",
                                  group_name=group_name)
            self.group_name = group_name
        return self.rank

    def teardown(self) -> None:
        if self.group_name is not None:
            from ray_tpu.collective.collective import (
                destroy_collective_group,
            )

            try:
                destroy_collective_group(self.group_name)
            except Exception:
                pass
            self.group_name = None

    # -- PPO update ---------------------------------------------------------
    def _batch(self, experiences: Sequence[Experience]):
        import numpy as np

        prefix = list(self.hyper["prefix"])
        B = self.hyper["pad_batch"]
        T = self.hyper["pad_tokens"]
        exps = sorted(experiences, key=lambda e: e.seq_no)
        shard = exps[self.rank::self.world]
        if len(shard) > B:
            raise ValueError(
                f"rank {self.rank} shard {len(shard)} exceeds pad_batch {B}")
        tokens = np.zeros((B, T), np.int32)
        resp_mask = np.zeros((B, T), np.float32)
        valid = np.zeros((B,), np.float32)
        rewards = np.zeros((B,), np.float32)
        for i, e in enumerate(shard):
            seq = (prefix + list(e.prompt) + list(e.response))[:T]
            tokens[i, :len(seq)] = seq
            lo = min(len(prefix) + len(e.prompt), T)
            resp_mask[i, lo:len(seq)] = 1.0
            valid[i] = 1.0
            rewards[i] = e.reward
        return tokens, resp_mask, valid, rewards, len(shard)

    def update(self, experiences: Sequence[Experience]) -> dict:
        """One PPO update over a batch of experiences. Shards by seq_no
        across ranks (deterministic for the cross-mode identity proof),
        mean-allreduces gradients over the gang, steps Adam."""
        import jax.numpy as jnp

        from ray_tpu.train.backend import allreduce_gradients

        tokens, resp_mask, valid, rewards, n = self._batch(experiences)
        tokens = jnp.asarray(tokens)
        resp_mask = jnp.asarray(resp_mask)
        valid = jnp.asarray(valid)
        rewards = jnp.asarray(rewards)
        old_logp, adv, ret, m, kl = self._stats_fn(
            self.policy, self.ref_lm, tokens, resp_mask, rewards, valid)
        loss = pg = vloss = 0.0
        for _ in range(self.hyper["ppo_epochs"]):
            (loss, (pg, vloss, _ent)), grads = self._grad_fn(
                self.policy, tokens, old_logp, adv, ret, m)
            if self.world > 1:
                grads = allreduce_gradients(grads,
                                            group_name=self.group_name)
            self.policy, self.opt_state = self._apply_fn(
                grads, self.opt_state, self.policy)
        self.version += 1
        return {"version": self.version, "loss": float(loss),
                "pg_loss": float(pg), "vf_loss": float(vloss),
                "kl": float(kl),
                "reward_mean": float(rewards.sum() / max(1, n)),
                "n": n}

    # -- weight sync / introspection ----------------------------------------
    def get_lm_meta(self) -> List[dict]:
        return self.lm_meta

    def publish(self, broadcast: bool = True, node_ids=None):
        """Rank 0: push the LM leaves into the object plane (and fan them
        out to the generator nodes when broadcast=True). Returns the leaf
        refs — nested refs are owner-pinned until the caller consumes."""
        from ray_tpu.rlhf import weight_sync

        refs, stats = weight_sync.publish_weights(
            self.policy["lm"], self.lm_meta, broadcast=broadcast,
            node_ids=node_ids)
        return refs, stats, self.version, self.lm_meta

    def send_lm_channel(self, channel) -> int:
        """Rank 0, colocated mode: stream the LM leaves through the
        device channel (raw dlpack frames, no pickle)."""
        from ray_tpu.rlhf import weight_sync

        return weight_sync.send_weights_channel(
            channel, self.policy["lm"], self.lm_meta)

    def state_leaves(self):
        """Full (policy, optimizer) state as raw leaves, for the
        placement-switch hand-off to a fresh gang."""
        import jax
        import numpy as np

        leaves = [np.asarray(l) for l in
                  jax.tree_util.tree_leaves((self.policy, self.opt_state))]
        return leaves, self.version

    def state_snapshot(self, directory: Optional[str] = None):
        """`state_leaves` plus, when `directory` is set, an async durable
        snapshot of the same state through the checkpoint plane: the
        hand-off leaves are captured inline, the shard/manifest persist
        runs in the background while the replacement gang forms — so the
        drain-and-reform path gets crash durability without lengthening
        the switch."""
        leaves, version = self.state_leaves()
        if directory:
            from ray_tpu.checkpoint import CheckpointPlane

            if self._ckpt_plane is None:
                # Fresh buffers per save (no pool reuse): the returned
                # hand-off leaves and the staging copies are independent.
                self._ckpt_plane = CheckpointPlane(reuse_buffers=False,
                                                   source="rlhf")
            self._ckpt_plane.save_async(
                (self.policy, self.opt_state), directory,
                name="rlhf_state", rank=0, world=1, step=version)
        return leaves, version

    def flush_state_persist(self, timeout: float = 10.0) -> bool:
        """Wait for in-flight background state persists (teardown path)."""
        if self._ckpt_plane is None:
            return True
        return self._ckpt_plane.flush(timeout)

    def lm_leaves(self):
        """LM leaves (meta order) for bit-identity assertions."""
        import numpy as np

        from ray_tpu.rlhf import weight_sync

        return [np.asarray(l) for l in
                weight_sync.flatten_weights(self.policy["lm"], self.lm_meta)]

    def greedy_tokens(self, prompt, max_new_tokens: int = 8) -> List[int]:
        """Greedy continuation via the plain (non-paged) forward — the
        learner-side half of the engine/learner bit-identity probe."""
        import jax.numpy as jnp

        from ray_tpu.models import llama

        tokens = list(prompt)
        for _ in range(max_new_tokens):
            logits = llama.forward(
                self.policy["lm"], jnp.asarray([tokens], dtype=jnp.int32),
                self.config)
            tokens.append(int(jnp.argmax(logits[0, -1])))
        return tokens[len(prompt):]

    def ping(self) -> int:
        return self.rank


class RLHFTrainer:
    """Drives the full loop: rollout round -> queue -> learner gang ->
    weight sync -> placement decision. See module docstring."""

    def __init__(self, config: RLHFConfig):
        import jax.numpy as jnp

        from ray_tpu.models import llama
        from ray_tpu.train.telemetry import TrainTelemetry
        from ray_tpu.util.queue import Queue

        if config.placement_mode not in MODES + (ADAPTIVE,):
            raise ValueError(
                f"placement_mode must be one of {MODES + (ADAPTIVE,)}, "
                f"got {config.placement_mode!r}")
        self.config = config
        kwargs = dict(config.model_kwargs)
        kwargs.setdefault("dtype", jnp.float32)
        self.model_config = llama.LlamaConfig.tiny(**kwargs)

        self.mode = (config.initial_mode
                     if config.placement_mode == ADAPTIVE
                     else config.placement_mode)
        self.policy = None
        if config.placement_mode == ADAPTIVE:
            self.policy = config.placement_policy or PlacementPolicy()

        self.coordinator = RolloutCoordinator()
        self.queue = Queue()
        self.telemetry = TrainTelemetry(config.run_name)
        self.epoch = 0
        self.version = 0
        self.updates_total = 0
        self.switches: List[dict] = []
        self.update_stats: List[dict] = []
        self.consumed_seq_nos: List[int] = []
        self.sync_ms: List[float] = []
        self.generator_rebuilds = 0
        self._seen_drain_events: set = set()

        self.learners: List = []
        self.generators: List = []
        self.local_gen: Optional[RolloutReplica] = None
        self.lm_meta: Optional[List[dict]] = None
        self.loop = None
        self._loop_target = 0

        vocab = self.model_config.vocab_size
        self._prompt_fn = (config.prompt_fn or
                           (lambda i: default_prompt_fn(
                               i, config.prompt_len, vocab)))
        self._prompt_index = 0
        self._prompt_stream = None   # lazy StreamingIterator (prompt_dataset)
        self._prompt_buf: List[List[int]] = []
        self._hyper = {
            "lr": config.lr, "clip_eps": config.clip_eps,
            "kl_coef": config.kl_coef, "gamma": config.gamma,
            "lam": config.lam, "vf_coef": config.vf_coef,
            "ent_coef": config.ent_coef, "ppo_epochs": config.ppo_epochs,
            "pad_batch": max(1, math.ceil(config.prompts_per_iter
                                          / config.learner_world)),
            "pad_tokens": (len(config.system_prompt) + config.prompt_len
                           + config.max_new_tokens),
            "prefix": list(config.system_prompt),
        }
        self._rollout_kwargs = {
            "system_prompt": tuple(config.system_prompt),
            "max_new_tokens": config.max_new_tokens,
            "temperature": config.temperature,
            "base_seed": config.seed,
            "reward_fn": config.reward_fn or default_reward,
        }

    # -- gang formation -----------------------------------------------------
    def _form_learners(self, init_leaves, start_version: int) -> None:
        import ray_tpu

        cfg = self.config
        self.group_name = f"{cfg.run_name}-g{self.epoch}"
        cls = ray_tpu.remote(LearnerWorker)
        self.learners = [
            cls.options(**(cfg.learner_options or {})).remote(
                rank, cfg.learner_world, cfg.model_kwargs, self._hyper,
                cfg.seed, init_leaves, start_version)
            for rank in range(cfg.learner_world)]
        # Rendezvous concurrently: submit every setup() before getting any.
        ray_tpu.get([l.setup.remote(self.group_name) for l in self.learners])
        self.lm_meta = ray_tpu.get(self.learners[0].get_lm_meta.remote())
        self.version = start_version

    def _form_generators(self, options: Optional[dict] = None) -> None:
        import ray_tpu

        cfg = self.config
        broadcast = self.mode == DISAGGREGATED
        refs, _stats, version, meta = ray_tpu.get(
            self.learners[0].publish.remote(broadcast=broadcast))
        gen_kwargs = dict(num_kv_blocks=cfg.num_kv_blocks,
                          block_size=cfg.block_size,
                          max_batch_size=cfg.max_batch_size,
                          weight_refs=refs, weight_meta=meta,
                          weights_version=version)
        if self.mode == COLOCATED:
            # Time-sliced with the learner gang: the engine lives in the
            # driver process and shares the slice's devices.
            self.local_gen = RolloutReplica(
                cfg.model_kwargs, self._rollout_kwargs,
                name=f"gen-local-e{self.epoch}", **gen_kwargs)
            self.generators = []
        else:
            cls = ray_tpu.remote(RolloutReplica)
            opts = options if options is not None else (
                cfg.generator_options or {})
            self.generators = [
                cls.options(**opts).remote(
                    cfg.model_kwargs, self._rollout_kwargs,
                    name=f"gen{i}-e{self.epoch}", **gen_kwargs)
                for i in range(cfg.num_generators)]
            ray_tpu.get([g.ping.remote() for g in self.generators])
            self.local_gen = None

    def _teardown_learners(self) -> None:
        import ray_tpu

        for l in self.learners:
            try:
                ray_tpu.get(l.teardown.remote())
            except Exception:
                pass
            try:
                ray_tpu.kill(l)
            except Exception:
                pass
        self.learners = []

    def _teardown_generators(self) -> None:
        import ray_tpu

        for g in self.generators:
            try:
                ray_tpu.kill(g)
            except Exception:
                pass
        self.generators = []
        self.local_gen = None

    # -- learner loop -------------------------------------------------------
    def _start_loop(self) -> None:
        from ray_tpu.train.learner import QueueLearnerLoop

        self.loop = QueueLearnerLoop(self.queue, self._apply_batch).start()
        self._loop_target = 0

    def _apply_batch(self, batch: List[Experience]) -> None:
        import ray_tpu

        refs = [l.update.remote(batch) for l in self.learners]
        stats = ray_tpu.get(refs)
        self.version = stats[0]["version"]
        self.update_stats.append(stats[0])
        self.updates_total += 1
        self.consumed_seq_nos.extend(e.seq_no for e in batch)

    # -- rollout round ------------------------------------------------------
    def _rollout_round(self) -> List[Experience]:
        import ray_tpu

        cfg = self.config
        coord = self.coordinator
        while not coord.round_complete():
            if self.mode == COLOCATED:
                items = coord.take(cfg.prompts_per_iter)
                if items:
                    coord.complete(self.local_gen.generate(items))
                continue
            per = max(1, math.ceil(
                coord.pending_count / max(1, len(self.generators))))
            shards = []
            failed = False
            for g in self.generators:
                items = coord.take(per)
                if not items:
                    continue
                try:
                    ref = g.generate.remote(items)
                except Exception:
                    # Actor already known-dead: submission itself raises.
                    coord.requeue([s for s, _ in items])
                    failed = True
                    continue
                shards.append((items, ref))
            for items, ref in shards:
                try:
                    coord.complete(ray_tpu.get(
                        ref, timeout=cfg.rollout_get_timeout))
                except Exception:
                    # Generator died mid-batch (slice loss, actor death,
                    # timeout): its incomplete seq_nos go back to the
                    # front of the queue; duplicates from a straggling
                    # reply are dropped by the ledger.
                    coord.requeue([s for s, _ in items])
                    failed = True
            if failed:
                self._rebuild_generators()
        return coord.drain_done()

    def _rebuild_generators(self) -> None:
        from ray_tpu.runtime import events

        self.generator_rebuilds += 1
        if self.generator_rebuilds > self.config.max_generator_rebuilds:
            raise RuntimeError(
                f"generator gang failed {self.generator_rebuilds} times")
        events.emit(
            events.TRAIN_GANG_RESTART,
            f"rlhf run {self.config.run_name!r}: generator gang lost, "
            f"re-forming (rebuild #{self.generator_rebuilds})",
            severity="WARNING", source="rlhf",
            labels={"run": self.config.run_name,
                    "epoch": str(self.epoch),
                    "rebuild": str(self.generator_rebuilds)})
        self._teardown_generators()
        # Re-forming in the seconds after a slice death races the control
        # plane: the object location table and actor directory can still
        # reference the dead node, so the fresh publish/broadcast may fail
        # transiently (location-unknown, late slice-lost surfacing). Those
        # clear on their own — retry instead of burning the rebuild budget.
        last_exc = None
        for attempt in range(3):
            try:
                self._form_generators(
                    options=self.config.generator_fallback_options)
                return
            except Exception as exc:
                last_exc = exc
                self._teardown_generators()
                time.sleep(1.0 + attempt)
        raise RuntimeError(
            "generator gang re-formation failed after retries") from last_exc

    # -- weight sync --------------------------------------------------------
    def _sync_weights(self) -> float:
        import ray_tpu

        t0 = time.perf_counter()
        if self.mode == COLOCATED:
            from ray_tpu.dag.device_channel import DeviceChannel
            from ray_tpu.rlhf import weight_sync

            # Learner rank 0 streams leaves while we read: capacity covers
            # the whole tree so the writer never blocks on the ring.
            channel = DeviceChannel(capacity=len(self.lm_meta) + 1)
            send_ref = self.learners[0].send_lm_channel.remote(channel)
            weight_sync.colocated_hot_swap(
                self.local_gen.engine, None, self.lm_meta,
                version=self.version, channel=channel)
            ray_tpu.get(send_ref)
        else:
            refs, _stats, version, meta = ray_tpu.get(
                self.learners[0].publish.remote(broadcast=True))
            ray_tpu.get([g.sync_weights.remote(refs, meta, version)
                         for g in self.generators])
        ms = (time.perf_counter() - t0) * 1e3
        self.sync_ms.append(ms)
        return ms

    # -- placement switch ---------------------------------------------------
    def _switch(self, to_mode: str, reason: str, iteration: int) -> None:
        import ray_tpu

        from ray_tpu.runtime import events

        self.coordinator.requeue_all_issued()
        self.loop.stop(drain=True)  # STOP barrier: queued batches apply first
        # Hand-off leaves come back inline; when state_checkpoint_dir is
        # set the same state also persists durably in the background (the
        # switch only ever waits for the snapshot, never the I/O).
        leaves, version = ray_tpu.get(
            self.learners[0].state_snapshot.remote(
                self.config.state_checkpoint_dir))
        self._teardown_generators()
        if self.config.state_checkpoint_dir:
            try:
                ray_tpu.get(self.learners[0].flush_state_persist.remote(),
                            timeout=30)
            except Exception:
                pass  # durability is best-effort; the hand-off leaves rule
        self._teardown_learners()
        from_mode, self.mode = self.mode, to_mode
        self.epoch += 1
        self._form_learners(leaves, version)
        self._form_generators()
        self._start_loop()
        events.emit(
            events.RLHF_PLACEMENT_SWITCH,
            f"rlhf run {self.config.run_name!r}: {from_mode} -> {to_mode} "
            f"after iteration {iteration} ({reason})",
            severity="INFO", source="rlhf",
            labels={"run": self.config.run_name, "from_mode": from_mode,
                    "to_mode": to_mode, "reason": reason,
                    "epoch": str(self.epoch), "iteration": str(iteration)})
        self.switches.append({"iteration": iteration, "from": from_mode,
                              "to": to_mode, "reason": reason,
                              "epoch": self.epoch})

    def _engine_stats(self) -> Optional[dict]:
        import ray_tpu

        try:
            if self.mode == COLOCATED and self.local_gen is not None:
                return self.local_gen.engine_stats()
            if self.generators:
                return ray_tpu.get(self.generators[0].engine_stats.remote(),
                                   timeout=10)
        except Exception:
            pass
        return None

    def _drain_notice(self) -> Optional[str]:
        """Fresh NODE_DRAINING notice covering a node hosting one of this
        run's learner/generator actors, or None.

        The proactive half of advance-notice preemption for RLHF gangs:
        the re-form happens on live capacity BEFORE the deadline kill,
        instead of surfacing later as a collective abort mid-update.
        Best-effort — drain awareness must never fail the PPO loop."""
        from ray_tpu.core import worker as worker_mod
        from ray_tpu.runtime import events as events_mod

        try:
            core = worker_mod.global_worker()
            fresh: Dict[str, str] = {}
            for ev in core.io.run(core.gcs.call(
                    "list_events", event_type=events_mod.NODE_DRAINING,
                    limit=20), timeout=5):
                key = (ev.get("node_id"), ev.get("time"))
                if key in self._seen_drain_events or not ev.get("node_id"):
                    continue
                self._seen_drain_events.add(key)
                fresh[ev["node_id"]] = ev.get("message", "node draining")
            if not fresh:
                return None
            ours = {h._actor_id
                    for h in list(self.learners) + list(self.generators)
                    if hasattr(h, "_actor_id")}
            homes = set()
            for a in core.io.run(core.gcs.call("list_actors"), timeout=5):
                if a.get("actor_id") in ours and a.get("node_id"):
                    homes.add(a["node_id"].hex())
            for node_hex, msg in fresh.items():
                if node_hex in homes:
                    return msg
        except Exception:
            pass
        return None

    def _maybe_switch(self, iteration: int, rollout_s: float,
                      update_s: float) -> None:
        cfg = self.config
        if iteration == cfg.iterations - 1:
            return  # nothing left to run in the new placement
        notice = self._drain_notice()
        if notice:
            if self.policy is not None:
                # Route through the policy so its dwell/mode state stays
                # consistent with the forced re-form.
                self.policy.note_drain(notice)
                decision = self.policy.decide(
                    rollout_s, update_s, self._engine_stats(), self.mode)
                self._switch(decision.mode, decision.reason, iteration)
            else:
                self._switch(self.mode, f"drain re-form: {notice}",
                             iteration)
            return
        if cfg.force_switch_at is not None:
            if iteration == cfg.force_switch_at:
                other = (DISAGGREGATED if self.mode == COLOCATED
                         else COLOCATED)
                self._switch(other, "forced", iteration)
            return
        if self.policy is None:
            return
        from ray_tpu.config import cfg as rt_cfg

        interval = rt_cfg().rlhf_placement_check_interval
        if (iteration + 1) % max(1, interval) != 0:
            return
        decision = self.policy.decide(rollout_s, update_s,
                                      self._engine_stats(), self.mode)
        if decision.switch:
            self._switch(decision.mode, decision.reason, iteration)

    def _next_prompts(self, count: int) -> List[List[int]]:
        """The next `count` prompts. With a prompt_dataset, rows stream
        through the pipelined data plane — prefetch keeps the next batch
        materializing while rollouts run — and the set cycles at epoch
        end. Without one, the synthetic prompt_fn stream."""
        cfg = self.config
        if cfg.prompt_dataset is None:
            base = self._prompt_index
            self._prompt_index += count
            return [self._prompt_fn(base + i) for i in range(count)]
        out: List[List[int]] = []
        while len(out) < count:
            if self._prompt_buf:
                out.append(self._prompt_buf.pop(0))
                continue
            if self._prompt_stream is None:
                self._prompt_stream = cfg.prompt_dataset.iter_batches(
                    batch_size=max(count, 1), prefetch_batches=2)
            try:
                batch = next(self._prompt_stream)
            except StopIteration:
                self._prompt_stream = None  # epoch exhausted: cycle
                continue
            col = (batch[cfg.prompt_column]
                   if cfg.prompt_column in batch
                   else next(iter(batch.values())))
            for row in col:
                toks = row.tolist() if hasattr(row, "tolist") else row
                if not isinstance(toks, list):
                    toks = [toks]
                self._prompt_buf.append([int(t) for t in toks])
        return out

    def _close_prompt_stream(self) -> None:
        if self._prompt_stream is not None:
            try:
                self._prompt_stream.stop()
            except Exception:
                pass
            self._prompt_stream = None

    # -- main loop ----------------------------------------------------------
    def run(self) -> dict:
        cfg = self.config
        t_run = time.perf_counter()
        self._form_learners(None, 0)
        self._form_generators()
        self._start_loop()
        modes: List[str] = []
        rollout_tokens: Dict[int, Dict[int, List[int]]] = {}
        try:
            for it in range(cfg.iterations):
                t_iter = time.perf_counter()
                prompts = self._next_prompts(cfg.prompts_per_iter)
                self.coordinator.add_prompts(prompts)

                t0 = time.perf_counter()
                exps = self._rollout_round()
                rollout_s = time.perf_counter() - t0
                rollout_tokens[it] = {e.seq_no: list(e.response)
                                      for e in exps}

                t1 = time.perf_counter()
                self.queue.put(exps)
                self._loop_target += 1
                self.loop.wait_for(self._loop_target,
                                   timeout=cfg.update_wait_timeout)
                update_s = time.perf_counter() - t1

                sync_ms = self._sync_weights()
                modes.append(self.mode)
                self.telemetry.record_step({
                    "step": it, "rank": 0,
                    "total_s": time.perf_counter() - t_iter,
                    "data_s": rollout_s,          # rollout phase
                    "compute_s": update_s,        # PPO update phase
                    "collective_s": 0.0, "checkpoint_s": 0.0,
                    "other_s": sync_ms / 1e3,     # weight sync phase
                })
                self._maybe_switch(it, rollout_s, update_s)
            self.loop.stop(drain=True)
        except Exception:
            self.shutdown()
            raise
        finally:
            self._close_prompt_stream()
        # Wall time spans gang formation, switches, and rebuilds, so
        # placement churn dilutes goodput exactly like Train restarts do.
        self.telemetry.wall_time_s = time.perf_counter() - t_run
        return {
            "iterations": cfg.iterations,
            "modes": modes,
            "switches": list(self.switches),
            "ledger": self.coordinator.ledger(),
            "consumed_seq_nos": sorted(self.consumed_seq_nos),
            "updates_applied": self.updates_total,
            "rollout_tokens": rollout_tokens,
            "final_version": self.version,
            "update_stats": list(self.update_stats),
            "sync_ms": list(self.sync_ms),
            "generator_rebuilds": self.generator_rebuilds,
            "goodput": self.telemetry.goodput,
        }

    # -- probes (tests / benchmarks) ----------------------------------------
    def learner_lm_leaves(self):
        import ray_tpu

        return ray_tpu.get(self.learners[0].lm_leaves.remote())

    def generator_lm_leaves(self):
        import numpy as np

        import ray_tpu
        from ray_tpu.rlhf import weight_sync

        if self.mode == COLOCATED:
            params = self.local_gen.engine.runner.params
            return [np.asarray(l) for l in
                    weight_sync.flatten_weights(params, self.lm_meta)]
        return ray_tpu.get(self.generators[0].lm_leaves.remote(self.lm_meta))

    def generator_greedy(self, prompt, max_new_tokens: int = 8):
        import ray_tpu

        if self.mode == COLOCATED:
            return self.local_gen.greedy_tokens(prompt, max_new_tokens)
        return ray_tpu.get(self.generators[0].greedy_tokens.remote(
            prompt, max_new_tokens))

    def shutdown(self) -> None:
        self._close_prompt_stream()
        if self.loop is not None:
            try:
                self.loop.stop(drain=False)
            except Exception:
                pass
            self.loop = None
        self._teardown_generators()
        self._teardown_learners()
        try:
            self.queue.shutdown()
        except Exception:
            pass
