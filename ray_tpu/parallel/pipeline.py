"""Pipeline parallelism: layer-partitioned stages + 1F1B microbatch schedule.

Reference analog: the reference provides PP only as a substrate — compiled
DAGs with a static per-actor schedule (python/ray/dag/compiled_dag_node.py:767,
dag_node_operation.py:17-34) plus vLLM's internal PP placement
(vllm_models.py:121-131). Here PP is first-class and deliberately NOT a mesh
axis (see parallel/mesh.py): stages are separate programs — on separate
devices in one process (LocalPipeline: the dryrun/test path and the
single-host multi-chip path) or separate actors (ActorPipeline: the
multi-host path, activations handed off through compiled-graph
DeviceChannels from a static per-actor READ/COMPUTE/WRITE schedule — no
host pickling in the steady state).

Memory model: full activation recomputation — backward re-runs the stage
forward from the saved stage INPUT (cheap to store), so live memory per
stage is bounded by the 1F1B in-flight microbatch count, independent of
model depth.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


# ------------------------------------------------------------- partitioning

def stage_layer_ranges(n_layers: int, n_stages: int) -> List[Tuple[int, int]]:
    """Split layers into contiguous per-stage ranges (balanced, remainder to
    the earlier stages which also don't carry the lm_head)."""
    base, extra = divmod(n_layers, n_stages)
    ranges, start = [], 0
    for s in range(n_stages):
        size = base + (1 if s < extra else 0)
        ranges.append((start, start + size))
        start += size
    return ranges


def split_params(params: Dict, n_stages: int) -> List[Dict]:
    """Slice a stacked-layer Llama param tree into per-stage trees. Stage 0
    holds the embedding; the last stage holds final_norm + lm_head."""
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    ranges = stage_layer_ranges(n_layers, n_stages)
    stages = []
    for s, (lo, hi) in enumerate(ranges):
        st: Dict[str, Any] = {
            "layers": jax.tree.map(lambda x: x[lo:hi], params["layers"])}
        if s == 0:
            st["embed"] = params["embed"]
        if s == n_stages - 1:
            st["final_norm"] = params["final_norm"]
            st["lm_head"] = params["lm_head"]
        stages.append(st)
    return stages


def merge_params(stage_params: List[Dict]) -> Dict:
    """Inverse of split_params (checkpoint save / single-device eval)."""
    layers = jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0),
        *[st["layers"] for st in stage_params])
    return {"embed": stage_params[0]["embed"], "layers": layers,
            "final_norm": stage_params[-1]["final_norm"],
            "lm_head": stage_params[-1]["lm_head"]}


# ------------------------------------------------------------ stage programs

def stage_apply(stage_params: Dict, x, config, *, is_first: bool,
                is_last: bool):
    """One stage's forward: tokens -> hidden (first), hidden -> hidden
    (middle), hidden -> logits (last)."""
    from ray_tpu.models import llama as llama_mod
    from ray_tpu.ops.layers import rms_norm, rope_frequencies

    cos, sin = rope_frequencies(config.head_dim, config.max_seq,
                                config.rope_theta)
    if is_first:
        x = stage_params["embed"][x].astype(config.dtype)

    layer_fn = partial(llama_mod._layer, config)
    if config.remat:
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable)

    def body(h, lp):
        return layer_fn(h, lp, cos, sin), None

    x, _ = jax.lax.scan(body, x, stage_params["layers"])
    if is_last:
        x = rms_norm(x, stage_params["final_norm"], config.norm_eps)
        x = (x @ stage_params["lm_head"].astype(config.dtype)).astype(
            jnp.float32)
    return x


def last_stage_loss(stage_params: Dict, x, targets, config,
                    is_first: bool = False):
    from ray_tpu.models.llama import next_token_ce

    logits = stage_apply(stage_params, x, config, is_first=is_first,
                         is_last=True)
    return next_token_ce(logits, targets)


def build_chunk_programs(config, chunk_ids, n_virtual: int):
    """Jitted per-chunk programs shared by LocalPipeline and
    PipelineStageActor: fwd[c] (None for the last chunk — its loss+grads
    come from bwd[c]) and bwd[c] (value_and_grad of the loss for the last
    chunk; vjp of the stage forward otherwise)."""
    fwd: Dict[int, Any] = {}
    bwd: Dict[int, Any] = {}
    for c in chunk_ids:
        is_first, is_last = c == 0, c == n_virtual - 1
        if is_last:
            def loss_f(p, x, t, _first=is_first):
                return last_stage_loss(p, x, t, config, is_first=_first)

            fwd[c] = None
            bwd[c] = jax.jit(jax.value_and_grad(loss_f, argnums=(0, 1)))
        else:
            f = partial(stage_apply, config=config, is_first=is_first,
                        is_last=False)
            fwd[c] = jax.jit(f)

            def bwd_f(p, x, g, _f=f):
                out, vjp = jax.vjp(lambda pp, xx: _f(pp, xx), p, x)
                return vjp(g)

            bwd[c] = jax.jit(bwd_f)
    return fwd, bwd


# --------------------------------------------------------------- schedule

@dataclasses.dataclass(frozen=True)
class PipeOp:
    kind: str        # "fwd" | "bwd"
    stage: int
    microbatch: int


def one_f_one_b(n_stages: int, n_microbatches: int) -> List[List[PipeOp]]:
    """Per-stage 1F1B op sequences (the static schedule a compiled DAG would
    carry, dag_node_operation.py:17). Stage s runs (n_stages - s) warmup
    forwards, then alternates 1F1B, then drains backwards."""
    assert n_microbatches >= n_stages, \
        "1F1B needs at least n_stages microbatches"
    per_stage: List[List[PipeOp]] = []
    for s in range(n_stages):
        ops: List[PipeOp] = []
        warmup = n_stages - s
        f = b = 0
        for _ in range(min(warmup, n_microbatches)):
            ops.append(PipeOp("fwd", s, f))
            f += 1
        while f < n_microbatches:
            ops.append(PipeOp("bwd", s, b))
            b += 1
            ops.append(PipeOp("fwd", s, f))
            f += 1
        while b < n_microbatches:
            ops.append(PipeOp("bwd", s, b))
            b += 1
        per_stage.append(ops)
    return per_stage


def virtual_stage_schedule(n_devices: int, v: int,
                           n_microbatches: int) -> List[List[PipeOp]]:
    """Per-DEVICE op sequences for a VIRTUAL-stage pipeline: the model is
    cut into n_devices*v chunks; device d hosts chunks d, d+n_devices, ...
    (round-robin, the Megatron virtual-pipeline PLACEMENT — it balances
    per-device memory and enables finer microbatch granularity).

    The op order is depth-(n_devices*v) 1F1B restricted to each device —
    the simple baseline kept for comparison in the bubble-accounting test;
    production paths use megatron_interleaved_schedule below, which hits
    the (p-1)/(v*m) interleaved bubble bound. PipeOp.stage is the VIRTUAL
    stage (chunk) id; device = stage % n_devices. Requires
    n_microbatches >= n_devices * v."""
    n_virtual = n_devices * v
    per_device: List[List[PipeOp]] = [[] for _ in range(n_devices)]
    for op in global_order(n_virtual, n_microbatches):
        per_device[op.stage % n_devices].append(op)
    return per_device


def megatron_interleaved_schedule(n_devices: int, v: int,
                                  n_microbatches: int) -> List[List[PipeOp]]:
    """Per-DEVICE op sequences for the Megatron interleaved 1F1B schedule
    (Narayanan et al. 2021; Megatron-LM schedules.py): chunks placed as in
    virtual_stage_schedule, but the op ORDER cycles microbatch groups of
    size n_devices through the v local chunks — warmup of
    (p-d-1)*2 + (v-1)*p forwards, then fwd/bwd steady state, then drain.
    Simulation-validated properties (see tests): deadlock-free under
    blocking in-order per-device execution, complete (one fwd + one bwd
    per chunk x microbatch), and a pipeline bubble of 2*(p-1)/v ticks vs
    2*(p*v-1) for the plain virtual order. Requires m % p == 0."""
    p, total = n_devices, n_microbatches * v
    assert n_microbatches % p == 0, \
        "interleaved schedule needs n_microbatches % n_devices == 0"

    def chunk_of(op_id: int, forward: bool) -> int:
        c = (op_id % (p * v)) // p
        return c if forward else (v - 1 - c)

    def mb_of(op_id: int) -> int:
        return (op_id // (p * v)) * p + op_id % p

    out: List[List[PipeOp]] = []
    for d in range(p):
        ops: List[PipeOp] = []
        warmup = min((p - d - 1) * 2 + (v - 1) * p, total)
        f = b = 0
        for _ in range(warmup):
            ops.append(PipeOp("fwd", chunk_of(f, True) * p + d, mb_of(f)))
            f += 1
        while f < total:
            ops.append(PipeOp("fwd", chunk_of(f, True) * p + d, mb_of(f)))
            f += 1
            ops.append(PipeOp("bwd", chunk_of(b, False) * p + d, mb_of(b)))
            b += 1
        while b < total:
            ops.append(PipeOp("bwd", chunk_of(b, False) * p + d, mb_of(b)))
            b += 1
        out.append(ops)
    return out


def linearize(per_queue: List[List[PipeOp]], n_virtual: int) -> List[PipeOp]:
    """Merge per-queue op sequences into one dependency-valid global order,
    preserving each queue's internal order (queues = stages or devices).
    fwd(s, m) needs fwd(s-1, m); bwd(s, m) needs fwd(s, m) and
    bwd(s+1, m). Asserts the sequences are deadlock-free."""
    cursors = [0] * len(per_queue)
    done = set()
    order: List[PipeOp] = []
    total = sum(len(ops) for ops in per_queue)
    while len(order) < total:
        progressed = False
        for q in range(len(per_queue)):
            while cursors[q] < len(per_queue[q]):
                op = per_queue[q][cursors[q]]
                if op.kind == "fwd":
                    ready = (op.stage == 0
                             or ("fwd", op.stage - 1, op.microbatch) in done)
                else:
                    ready = (("fwd", op.stage, op.microbatch) in done
                             and (op.stage == n_virtual - 1
                                  or ("bwd", op.stage + 1,
                                      op.microbatch) in done))
                if not ready:
                    break
                done.add((op.kind, op.stage, op.microbatch))
                order.append(op)
                cursors[q] += 1
                progressed = True
        assert progressed, "pipeline schedule deadlocked"
    return order


def global_order(n_stages: int, n_microbatches: int) -> List[PipeOp]:
    """A single sequential order respecting all inter-stage dependencies
    (for single-process execution): fwd(s, m) after fwd(s-1, m); bwd(s, m)
    after bwd(s+1, m) and fwd(s, m)."""
    return linearize(one_f_one_b(n_stages, n_microbatches), n_stages)


def submission_order(n_devices: int, interleave: int,
                     n_microbatches: int) -> List[PipeOp]:
    """The dependency-valid GLOBAL linearization whose per-device
    subsequence is the production schedule: plain 1F1B without
    interleaving, Megatron interleaved steady state with it. Shared by
    LocalPipeline (execution order) and build_stage_plans (each stage
    loop's static schedule is its subsequence of this order)."""
    if interleave <= 1:
        return global_order(n_devices, n_microbatches)
    if n_microbatches % n_devices != 0:
        # Megatron's interleaved order needs m % p == 0; other microbatch
        # counts (legal for the plain order: only m >= p*v) fall back to
        # depth-p*v 1F1B rather than rejecting the step.
        return global_order(n_devices * interleave, n_microbatches)
    per_device = megatron_interleaved_schedule(
        n_devices, interleave, n_microbatches)
    return linearize(per_device, n_devices * interleave)


# ---------------------------------------------------------- local pipeline

class LocalPipeline:
    """Stages on distinct devices of one process (ICI p2p on real hardware;
    host transfer on CPU test meshes). Used by dryrun_multichip's pp leg."""

    def __init__(self, config, params, n_stages: int, optimizer,
                 devices: Optional[Sequence] = None, interleave: int = 1):
        """`interleave=v` enables virtual-stage partitioning: layers split
        into n_stages*v chunks, chunk c on device c % n_stages (see
        virtual_stage_schedule). train_step then needs n_microbatches >=
        n_stages * v."""
        self.config = config
        self.n_stages = n_stages
        self.n_virtual = n_stages * max(1, interleave)
        self.optimizer = optimizer
        devices = list(devices or jax.devices()[:n_stages])
        assert len(devices) >= n_stages
        self.devices = devices[:n_stages]
        # Device of each VIRTUAL stage (round-robin under interleaving).
        self.chunk_devices = [self.devices[c % n_stages]
                              for c in range(self.n_virtual)]
        stages = split_params(params, self.n_virtual)
        self.stage_params = [
            jax.device_put(st, d) for st, d in zip(stages, self.chunk_devices)]
        self.opt_states = [
            jax.device_put(optimizer.init(st), d)
            for st, d in zip(self.stage_params, self.chunk_devices)]
        fwd, bwd = build_chunk_programs(config, range(self.n_virtual),
                                        self.n_virtual)
        self._fwd = [fwd[c] for c in range(self.n_virtual)]
        self._bwd = [bwd[c] for c in range(self.n_virtual)]
        self._apply = jax.jit(
            lambda p, o, g: self._apply_impl(p, o, g))

    def _apply_impl(self, params, opt_state, grads):
        import optax

        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def train_step(self, tokens, n_microbatches: int) -> Dict[str, float]:
        """One 1F1B training step. tokens: (batch, seq+1) int32; batch must
        divide into n_microbatches."""
        B = tokens.shape[0]
        assert B % n_microbatches == 0
        assert n_microbatches >= self.n_virtual, (
            f"1F1B over {self.n_virtual} virtual stages "
            f"({self.n_stages} devices x interleave "
            f"{self.n_virtual // self.n_stages}) needs n_microbatches >= "
            f"{self.n_virtual}, got {n_microbatches}")
        mb = B // n_microbatches
        inputs = tokens[:, :-1]
        targets = tokens[:, 1:]
        saved_in: Dict[Tuple[int, int], Any] = {}
        fwd_out: Dict[Tuple[int, int], Any] = {}
        grads_in: Dict[Tuple[int, int], Any] = {}
        stage_grads: List[Any] = [None] * self.n_virtual
        losses = []
        last = self.n_virtual - 1
        interleave = self.n_virtual // self.n_stages
        for op in submission_order(self.n_stages, interleave,
                                   n_microbatches):
            s, m = op.stage, op.microbatch
            if op.kind == "fwd":
                if s == 0:
                    x = jax.device_put(inputs[m * mb:(m + 1) * mb],
                                       self.chunk_devices[0])
                else:
                    x = jax.device_put(fwd_out.pop((s - 1, m)),
                                       self.chunk_devices[s])
                saved_in[(s, m)] = x
                if s != last:
                    fwd_out[(s, m)] = self._fwd[s](self.stage_params[s], x)
            else:
                if s == last:
                    x = saved_in.pop((s, m))
                    t = jax.device_put(targets[m * mb:(m + 1) * mb],
                                       self.chunk_devices[s])
                    loss, (dp, dx) = self._bwd[s](self.stage_params[s], x, t)
                    losses.append(loss)
                else:
                    x = saved_in.pop((s, m))
                    g = jax.device_put(grads_in.pop((s, m)),
                                       self.chunk_devices[s])
                    dp, dx = self._bwd[s](self.stage_params[s], x, g)
                if s > 0:
                    grads_in[(s - 1, m)] = dx
                stage_grads[s] = dp if stage_grads[s] is None else jax.tree.map(
                    jnp.add, stage_grads[s], dp)
        # Optimizer step per stage (grads averaged over microbatches).
        scale = 1.0 / n_microbatches
        for s in range(self.n_virtual):
            g = jax.tree.map(lambda v: v * scale, stage_grads[s])
            self.stage_params[s], self.opt_states[s] = self._apply(
                self.stage_params[s], self.opt_states[s], g)
        return {"loss": float(sum(float(l) for l in losses) / len(losses))}

    def merged_params(self) -> Dict:
        return merge_params([jax.device_get(st) for st in self.stage_params])


# ---------------------------------------------------------- actor pipeline

def build_stage_plans(n_stages: int, interleave: int, n_microbatches: int):
    """Compile the static per-actor channel plans for one ActorPipeline
    configuration: the device-channel analog of CompiledDAG._build.

    Returns (plans, driver_channels). plans[d] is actor d's plan — its
    submission_order subsequence as ops wired to DeviceChannels, plus a
    trailing optimizer "apply" op (and, on the actor hosting the last
    chunk, a "loss_out" op that reports the step's mean loss), lowered to
    a static READ/COMPUTE/WRITE schedule (dag/schedule.py) that
    run_pipeline_loop replays once per train step. driver_channels holds
    the driver's ends: "in" (token microbatches -> chunk 0), "tgt"
    (targets -> last chunk), "loss" (mean step loss <- last chunk).

    Channel capacities admit a full step of in-flight traffic plus the
    next step's lead-in, so the only blocking reads are true data
    dependencies — the schedule order, not ring backpressure, is the
    overlap plan. FIFO channels need no microbatch tags: every schedule
    (plain 1F1B and Megatron interleaved) produces and consumes each
    boundary's microbatches in ascending order.
    """
    from ray_tpu.dag import schedule as dag_schedule
    from ray_tpu.dag.device_channel import DeviceChannel

    p, v, m = n_stages, max(1, interleave), n_microbatches
    n_virtual = p * v
    last = n_virtual - 1
    cap = 2 * m + 2
    in_ch = DeviceChannel(capacity=cap)
    tgt_ch = DeviceChannel(capacity=cap)
    loss_ch = DeviceChannel(capacity=4)
    act_ch = {s: DeviceChannel(capacity=cap) for s in range(n_virtual - 1)}
    grad_ch = {s: DeviceChannel(capacity=cap) for s in range(n_virtual - 1)}

    per_actor_ops: List[List[dict]] = [[] for _ in range(p)]
    for op in submission_order(p, v, m):
        s, mb_i = op.stage, op.microbatch
        entry = {"kind": op.kind, "chunk": s, "mb": mb_i, "reads": [],
                 "writes": [], "method": f"{op.kind}[c{s},m{mb_i}]"}
        if op.kind == "fwd":
            entry["reads"].append(("in", in_ch) if s == 0
                                  else ("act", act_ch[s - 1]))
            if s != last:
                entry["writes"].append(act_ch[s])
        else:
            entry["reads"].append(("tgt", tgt_ch) if s == last
                                  else ("grad", grad_ch[s]))
            if s > 0:
                entry["writes"].append(grad_ch[s - 1])
        per_actor_ops[s % p].append(entry)

    plans = []
    for d in range(p):
        ops = per_actor_ops[d]
        ops.append({"kind": "apply", "chunk": -1, "mb": -1, "reads": [],
                    "writes": [], "method": "apply_updates"})
        if last % p == d:
            ops.append({"kind": "loss_out", "chunk": -1, "mb": -1,
                        "reads": [], "writes": [loss_ch],
                        "method": "loss_out"})
        for i, o in enumerate(ops):
            o["node_id"] = i
        plan = {"ops": ops, "n_microbatches": m}
        plan["schedule"] = dag_schedule.compile_plan_schedule(plan)
        plans.append(plan)
    return plans, {"in": in_ch, "tgt": tgt_ch, "loss": loss_ch}


class PipelineStageActor:
    """Pipeline chunks hosted in an actor (multi-host PP). One actor per
    DEVICE/host; with interleaving it hosts several VIRTUAL stages
    (chunks). It runs the channel loop (run_pipeline_loop): device-resident
    hand-off, no host pickling of activations."""

    def __init__(self, chunk_ids, n_virtual: int, config_bytes: bytes,
                 chunk_params_bytes: bytes, opt_name: str = "adamw",
                 lr: float = 1e-3):
        import cloudpickle
        import optax

        self.config = cloudpickle.loads(config_bytes)
        self.chunk_ids = list(chunk_ids)
        self.n = n_virtual
        chunk_params = cloudpickle.loads(chunk_params_bytes)
        self.optimizer = (optax.adamw(lr) if opt_name == "adamw"
                          else optax.sgd(lr))
        self.params: Dict[int, Any] = {}
        self.opt_state: Dict[int, Any] = {}
        self._saved: Dict[Tuple[int, int], Any] = {}
        self._grads: Dict[int, Any] = {}
        for c, params in zip(self.chunk_ids, chunk_params):
            self.params[c] = params
            self.opt_state[c] = self.optimizer.init(params)
        self._fwd, self._bwd = build_chunk_programs(
            self.config, self.chunk_ids, n_virtual)

    def _accumulate(self, chunk: int, dp):
        cur = self._grads.get(chunk)
        self._grads[chunk] = dp if cur is None else jax.tree.map(
            jnp.add, cur, dp)

    def apply_updates(self, n_microbatches: int) -> bool:
        import optax

        for c in self.chunk_ids:
            g = jax.tree.map(lambda v: v / n_microbatches, self._grads[c])
            updates, self.opt_state[c] = self.optimizer.update(
                g, self.opt_state[c], self.params[c])
            self.params[c] = optax.apply_updates(self.params[c], updates)
        self._grads = {}
        return True

    def get_params_bytes(self) -> bytes:
        import cloudpickle

        return cloudpickle.dumps(
            [jax.device_get(self.params[c]) for c in self.chunk_ids])

    def _pipeline_compute(self, op: dict, inp: Dict[str, Any],
                          losses: List[float], n_microbatches: int):
        kind = op["kind"]
        if kind == "fwd":
            c, mb = op["chunk"], op["mb"]
            x = inp["in"] if "in" in inp else inp["act"]
            self._saved[(c, mb)] = x
            if self._fwd[c] is None:
                return None  # last chunk: loss + grads come from its bwd
            return self._fwd[c](self.params[c], x)
        if kind == "bwd":
            c, mb = op["chunk"], op["mb"]
            x = self._saved.pop((c, mb))
            if "tgt" in inp:
                loss, (dp, dx) = self._bwd[c](self.params[c], x, inp["tgt"])
                losses.append(float(loss))
            else:
                dp, dx = self._bwd[c](self.params[c], x, inp["grad"])
            self._accumulate(c, dp)
            return dx
        if kind == "apply":
            self.apply_updates(n_microbatches)
            return None
        if kind == "loss_out":
            # A jax scalar, not a float: the loss rides the device fast
            # path like every other steady-state value.
            return jnp.asarray(sum(losses) / max(1, len(losses)),
                               dtype=jnp.float32)
        raise ValueError(f"unknown pipeline op kind {kind!r}")

    def run_pipeline_loop(self, plan: dict) -> dict:
        """Persistent channel-driven stage loop — the ActorPipeline analog
        of dag/executor.run_loop. Replays the plan's static
        READ/COMPUTE/WRITE schedule once per train step until the driver
        closes the step-input channels, then cascades CLOSE downstream and
        returns {"steps", "steady_serialization"} — the latter is this
        process's serialization-counter delta over the post-warmup steps,
        which tests assert contains ZERO pickles."""
        from ray_tpu.core import serialization
        from ray_tpu.dag import schedule as dag_schedule
        from ray_tpu.dag.channel import ChannelClosed

        ops = plan["ops"]
        schedule = plan["schedule"]
        m = plan["n_microbatches"]
        read_chs = [ch for op in ops for _, ch in op["reads"]]
        write_chs = [ch for op in ops for ch in op["writes"]]
        steps = 0
        steady_base = None
        try:
            while True:
                losses: List[float] = []
                pending: Dict[int, Dict[str, Any]] = {}
                outputs: Dict[int, Any] = {}
                try:
                    for slot in schedule:
                        op = ops[slot.op_index]
                        if slot.type == dag_schedule.READ:
                            pending[slot.op_index] = {
                                role: ch.read() for role, ch in op["reads"]}
                        elif slot.type == dag_schedule.COMPUTE:
                            outputs[slot.op_index] = self._pipeline_compute(
                                op, pending.pop(slot.op_index, {}), losses, m)
                        else:  # WRITE
                            val = outputs.pop(slot.op_index)
                            for ch in op["writes"]:
                                ch.write(val)
                except ChannelClosed:
                    break
                steps += 1
                if steps == 1:
                    # Step 1 is warmup (jit compilation, channel opens);
                    # the zero-pickle invariant is asserted on the delta
                    # accumulated from here on.
                    steady_base = serialization.counter_snapshot()
        finally:
            # Mirror dag/executor.run_loop: tombstone our reads (unwedges
            # blocked upstream writers), CLOSE our writes (downstream
            # loops exit at their next read), then free retained buffers.
            for ch in read_chs:
                try:
                    ch.close_read()
                except BaseException:
                    pass
            for ch in write_chs:
                try:
                    ch.close_write(timeout=10)
                except BaseException:
                    pass
            for ch in read_chs:
                try:
                    ch.drain()
                except BaseException:
                    pass
        return {"steps": steps,
                "steady_serialization":
                    serialization.counter_delta(steady_base)
                    if steady_base is not None else None}


class ActorPipeline:
    """Driver-side coordinator for actor-hosted stages.

    Stages run persistent loops (run_pipeline_loop) over their static
    READ/COMPUTE/WRITE schedules, activations and gradients hand off
    stage-to-stage through DeviceChannels (raw device bytes, zero host
    pickling), and the driver only feeds token/target microbatches and
    reads back the step loss. `interleave=v` gives each actor v
    round-robin chunks in the Megatron interleaved order
    (megatron_interleaved_schedule), so each loop's schedule realizes the
    small-bubble plan.
    """

    def __init__(self, config, params, n_stages: int, *, lr: float = 1e-3,
                 resources_per_stage: Optional[dict] = None,
                 interleave: int = 1):
        import cloudpickle

        import ray_tpu

        self.config = config
        self.n_stages = n_stages
        self.interleave = max(1, interleave)
        self.n_virtual = n_stages * self.interleave
        self._loop_refs: List[Any] = []
        self._driver_ch: Optional[Dict[str, Any]] = None
        self._loop_m: Optional[int] = None
        self.stage_schedules: Dict[int, List[Any]] = {}
        self.last_loop_stats: Optional[List[dict]] = None
        chunks = split_params(params, self.n_virtual)
        Stage = ray_tpu.remote(PipelineStageActor)
        opts = resources_per_stage or {"num_cpus": 0}
        cfg_b = cloudpickle.dumps(config)
        self.actors = []
        for d in range(n_stages):
            ids = list(range(d, self.n_virtual, n_stages))
            self.actors.append(Stage.options(**opts).remote(
                ids, self.n_virtual, cfg_b,
                cloudpickle.dumps([chunks[c] for c in ids]), "adamw", lr))

    def _ensure_loops(self, n_microbatches: int) -> None:
        """(Re)launch the stage loops if none are running or the microbatch
        count changed (the static schedules are compiled per m)."""
        if self._loop_refs and self._loop_m == n_microbatches:
            return
        self._stop_loops()
        plans, chans = build_stage_plans(self.n_stages, self.interleave,
                                         n_microbatches)
        self.stage_schedules = {d: plans[d]["schedule"]
                                for d in range(self.n_stages)}
        self._driver_ch = chans
        self._loop_m = n_microbatches
        self._loop_refs = [self.actors[d].run_pipeline_loop.remote(plans[d])
                           for d in range(self.n_stages)]

    def _stop_loops(self) -> None:
        """Close the step-input channels; the loops finish in-flight work,
        cascade CLOSE downstream, and return their stats (retained in
        .last_loop_stats). A loop that died with an error raises it here."""
        import ray_tpu
        from ray_tpu.dag.channel import ChannelClosed

        if not self._loop_refs:
            return
        refs, self._loop_refs = self._loop_refs, []
        chs, self._driver_ch = self._driver_ch, None
        self._loop_m = None
        for k in ("in", "tgt"):
            try:
                chs[k].close_write(timeout=10)
            except BaseException:
                pass
        try:
            while True:
                chs["loss"].read(timeout=10)
        except (ChannelClosed, TimeoutError):
            pass
        try:
            chs["loss"].drain()
        except BaseException:
            pass
        self.last_loop_stats = ray_tpu.get(refs, timeout=120)

    def _raise_loop_error(self):
        """The loss channel closed mid-step: a stage loop died. Unwind the
        channels and surface the real task error."""
        import ray_tpu

        refs, self._loop_refs = self._loop_refs, []
        chs, self._driver_ch = self._driver_ch, None
        self._loop_m = None
        if chs is not None:
            try:
                chs["loss"].close_read()
            except BaseException:
                pass
            for k in ("in", "tgt"):
                try:
                    chs[k].close_write(timeout=5)
                except BaseException:
                    pass
        for ref in refs:
            try:
                ray_tpu.get(ref, timeout=30)
            except BaseException as e:  # noqa: BLE001 — surface task error
                raise e
        raise RuntimeError("pipeline stage loop exited unexpectedly")

    def shutdown(self) -> None:
        """Stop the stage loops. Idempotent; the actors
        survive and a later train_step relaunches the loops."""
        self._stop_loops()

    def train_step(self, tokens, n_microbatches: int) -> Dict[str, float]:
        import numpy as np

        from ray_tpu.dag.channel import ChannelClosed

        B = tokens.shape[0]
        assert B % n_microbatches == 0
        mb = B // n_microbatches
        inputs = np.asarray(tokens[:, :-1])
        targets = np.asarray(tokens[:, 1:])
        self._ensure_loops(n_microbatches)
        try:
            # jnp arrays so even the driver's feeds ride the device fast
            # path — the whole steady state is pickle-free.
            for i in range(n_microbatches):
                self._driver_ch["in"].write(
                    jnp.asarray(inputs[i * mb:(i + 1) * mb]), timeout=600)
            for i in range(n_microbatches):
                self._driver_ch["tgt"].write(
                    jnp.asarray(targets[i * mb:(i + 1) * mb]), timeout=600)
            loss = self._driver_ch["loss"].read(timeout=600)
        except ChannelClosed:
            self._raise_loop_error()
        return {"loss": float(loss)}

    def merged_params(self) -> Dict:
        import cloudpickle

        import ray_tpu

        # Channel loops occupy the actors' execution threads: stop them so
        # the get_params_bytes calls below can run.
        self._stop_loops()
        blobs = ray_tpu.get([a.get_params_bytes.remote()
                             for a in self.actors], timeout=600)
        # Each actor returns ITS chunks (ids d, d+p, ...): reassemble in
        # global chunk order before merging.
        chunks: List[Any] = [None] * self.n_virtual
        for d, blob in enumerate(blobs):
            lst = cloudpickle.loads(blob)
            for i, c in enumerate(range(d, self.n_virtual, self.n_stages)):
                chunks[c] = lst[i]
        return merge_params(chunks)
