"""Runner for training traffic (`kind` "train_steps"): the sharded train step
of `parallel/fsdp.build_train_step` + the family's loss on the configuration's
mesh, a new batch of seeded tokens drawn on the device each step.

Order of a run: the first step of a 2-layer cut at the published widths
against the plain reference's loss and `jax.grad` (on one device), then the
model: parameters in one jitted call from the seed, the step compiled,
`warm_steps` steps untimed, then the window. One step is kept in flight: step
i+1 is dispatched before step i's loss is waited for, so the device never
waits for the host, and every step still gets a completion time.
"""

from __future__ import annotations

import math
import os
import time
from typing import Dict, List

from harness import Run, load_module, new_run, note, seed32, traced

# First-step loss and gradient norm of the sharded bf16 step against the
# float32 reference on the same bf16 weights: relative difference. The
# program rounds activations to bf16 after every matmul and reduces across
# chips in another order; PR 22 held four chips against one to the same 2e-2.
TRAIN_REL_TOL = 2e-2
CHECK_LAYERS, CHECK_SEQ = 2, 512
TRACE_STEPS = 3
# What a family that trains brings beside the names every family has
# (README.md, "The family file").
TRAINING_NAMES = ("loss_fn", "param_logical_axes", "init_params")


def _build(family, sizes: Dict, deployment: Dict, devices):
    """(model config, mesh, init_fn, make_step) through the program's own
    entry points, as a trainer would call them; the model's loss and its
    parameters' logical axes are the family's."""
    import optax

    from ray_tpu.parallel.fsdp import build_train_step
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    mc = family.model_config(sizes)
    mesh_config = MeshConfig(**deployment["mesh"])
    mesh = build_mesh(mesh_config, devices=devices[:mesh_config.num_devices])
    optimizer = {"adamw": optax.adamw}[deployment["optimizer"]](
        deployment["learning_rate"])
    init_fn, make_step = build_train_step(
        lambda p, b: family.loss_fn(p, b, mc), optimizer, mesh,
        family.param_logical_axes(mc), {"tokens": ("batch", None)})
    return mc, mesh, init_fn, make_step


def _init_params(family, mc, seed: int):
    """The whole parameter tree in one jitted call, in the served type."""
    import jax

    return jax.jit(lambda key: family.init_params(mc, key))(
        jax.random.key(seed32(seed)))


def check_reference(family, sizes: Dict, deployment: Dict, seed: int,
                    devices) -> Dict:
    """Loss and gradient norm of the program's first step on a 2-layer cut
    (same widths, same mesh) against the reference's loss and `jax.grad`."""
    import jax

    t0 = time.time()
    cut = dict(sizes, num_hidden_layers=min(CHECK_LAYERS,
                                            sizes["num_hidden_layers"]))
    mc, mesh, init_fn, make_step = _build(family, cut, deployment, devices)
    params = _init_params(family, mc, seed)
    batch = max(1, math.prod(v for k, v in deployment["mesh"].items()
                             if k != "tp"))
    seq = min(CHECK_SEQ, sizes["max_position_embeddings"] // 2)
    tokens = jax.random.randint(jax.random.key(seed32(seed, 3)),
                                (batch, seq + 1), 0, sizes["vocab_size"])
    want_loss, want_norm = family.reference_loss_and_grad_norm(
        params, tokens, cut)
    t1 = time.time()
    state, shardings = init_fn(params)
    del params
    state, metrics = make_step(shardings)(state, {"tokens": tokens})
    got_loss, got_norm = float(metrics["loss"]), float(metrics["grad_norm"])
    del state
    rel = {"loss": abs(got_loss - want_loss) / abs(want_loss),
           "grad_norm": abs(got_norm - want_norm) / abs(want_norm)}
    return {"ok": all(math.isfinite(v) and v <= TRAIN_REL_TOL
                      for v in rel.values()),
            "rel_diff": rel, "tolerance": TRAIN_REL_TOL,
            "loss": [got_loss, want_loss], "grad_norm": [got_norm, want_norm],
            "layers": cut["num_hidden_layers"], "tokens": [batch, seq],
            "reference_s": round(t1 - t0, 3),
            "program_s": round(time.time() - t1, 3)}


def run_cell(ctx) -> Run:
    import jax

    config, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    sizes, deployment = config["sizes"], config["deployment"]
    family = load_module("families", config["family"])
    missing = [n for n in TRAINING_NAMES if not hasattr(family, n)]
    if missing:
        raise SystemExit(f"benchmark: families/{config['family']}.py brings "
                         f"no {', '.join(missing)}: this family cannot train")
    devices = jax.devices()[:ctx.chips]
    batch, seq = int(traffic["global_batch"]), int(traffic["seq"])
    run = new_run(ctx, tokens_per_step=batch * seq,
                  flops_per_token=family.train_flops_per_token(sizes, seq))

    run.checks["reference"] = check_reference(family, sizes, deployment, seed,
                                              devices)
    note(phase="checks", **run.checks)
    if not run.checks["reference"]["ok"]:
        run.problems.append(f"reference check failed: {run.checks}")

    t = time.time()
    mc, mesh, init_fn, make_step = _build(family, sizes, deployment, devices)
    params = _init_params(family, mc, seed)
    state, shardings = init_fn(params)
    del params
    step_fn = make_step(shardings)
    key = jax.random.key(seed32(seed, 1))

    def draw(i):
        return {"tokens": jax.random.randint(
            jax.random.fold_in(key, i), (batch, seq + 1), 0,
            sizes["vocab_size"])}

    lowered = step_fn.lower(state, jax.eval_shape(draw, 0))
    compiled = lowered.compile()
    text = compiled.as_text()
    make_batch = jax.jit(draw, out_shardings=compiled.input_shardings[0][1])
    note(phase="model", seconds=round(time.time() - t, 3),
         mesh={k: v for k, v in mesh.shape.items() if v > 1},
         pallas_kernels_in_step=text.count(
             'custom_call_target="tpu_custom_call"'),
         collectives={op: text.count(f" {op}(") + text.count(f" {op}-start(")
                      for op in ("all-gather", "reduce-scatter", "all-reduce")})
    if ctx.require_kernels and 'custom_call_target="tpu_custom_call"' not in text:
        run.problems.append("no Pallas kernel in the train step")
    del text, lowered

    steps: List[Dict] = []
    dispatched = 0

    def run_steps(stop_at: float = 0.0, count: int = 0) -> None:
        """Steps until the clock passes `stop_at`, or `count` steps; one in
        flight. Appends {i, t_dispatch, t_done, loss, grad_norm} for each."""
        nonlocal state, dispatched
        pending = None
        n = 0
        while (n < count) if count else (time.time() < stop_at):
            t_dispatch = time.time()
            state, metrics = compiled(state, make_batch(dispatched))
            if pending is not None:
                _finish(pending)
            pending = (dispatched, t_dispatch, metrics)
            dispatched += 1
            n += 1
        if pending is not None:
            _finish(pending)

    def _finish(pending) -> None:
        i, t_dispatch, metrics = pending
        loss = float(metrics["loss"])        # waits for the step
        steps.append({"i": i, "t_dispatch": t_dispatch, "t_done": time.time(),
                      "loss": loss, "grad_norm": float(metrics["grad_norm"])})

    run_steps(count=int(traffic["warm_steps"]))
    if ctx.trace:      # its own steps, before the window: tracing is not free
        run.trace = traced(os.path.join(ctx.out_dir, "trace"),
                           lambda: run_steps(count=TRACE_STEPS))
        if run.trace is not None:
            run.trace["steps_traced"] = TRACE_STEPS
        run.traced_steps = steps[-TRACE_STEPS:]
    warm = len(steps)
    run.t0 = time.time()
    run_steps(stop_at=run.t0 + ctx.seconds)
    run.t1 = steps[-1]["t_done"]
    run.steps = steps[warm:]
    bad = [s["i"] for s in steps if not math.isfinite(s["loss"])]
    if bad:
        run.problems.append(f"loss not finite at steps {bad}")
    note(phase="window", seconds=round(run.window_s, 3),
         steps=len(run.steps), warm_steps=warm,
         first_loss=steps[0]["loss"], last_loss=steps[-1]["loss"],
         memory_peak_bytes_by_device={
             str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices})
    return run


def host_intervals(run: Run):
    """For the idle gaps' labels: each traced step, from when the step before
    it completed (or it was dispatched) to when it completed."""
    out, last_done = [], 0.0
    for s in run.traced_steps:
        out.append((max(s["t_dispatch"], last_done), s["t_done"],
                    f"step {s['i']}"))
        last_done = s["t_done"]
    return out


def attempted_failed(run: Run):
    return len(run.steps), sum(1 for s in run.steps
                               if not math.isfinite(s["loss"]))
