"""Training cells on the pipeline mesh (stage x tensor, four chips).

The timed path is the repo's trainer over a ``ParallelSpec``:
``repro.train.steps.make_lm_train_step(cfg, NO_POLICY, opt,
parallel=spec_from_cli(mesh, wire))``, the builder that ``launch/train.py
--mesh ... --wire ...`` calls.  The layer stack runs as a GPipe pipeline
over the ``stage`` axis with the cell's microbatches, each stage
tensor-parallel over the ``tensor`` axis; every stage hop and every
tensor-parallel gather and scatter crosses its ring packed by the cell's
wire codec.  AdamW, rematerialised layers and donated state, as on one
chip.  The weights and the optimizer state are made on the devices in
the layout of ``repro.launch.mesh.train_state_shardings`` (layers split
over the stages and the tensor ring, the vocabulary over all chips), from
bench/weights.py's recipe, so that no chip ever holds the whole model.

Set-up, the window and the check follow bench/drivers/train_sim.py, whose
stepper, gaps and reference this driver reuses: the first ``check_steps``
steps give the losses, the first clipped gradient and the weights'
change as per-leaf norms; the window dispatches steps back to back for
``--seconds``; then the plain reference (the configuration's
``reference``, bench/reference/dense_lm_mesh.py) follows the same first
steps.  A traced run also leaves, for the ``*.mesh`` readers, each
stage's devices and the program's count of what each ring carries a step
(``repro.train.steps.mesh_wire_bytes``).
"""
from __future__ import annotations

import collections
import contextlib
import gc
import math
import time

import numpy as np
import jax
import jax.numpy as jnp

from bench import flops, harness, traffic, weights
from bench import trace as tracing
from bench.configs import model_config
from repro.train.steps import mesh_wire_bytes

_sim = harness.driver("train_sim")
stepper = _sim.stepper
gaps = _sim.gaps
reference = _sim.reference


def parallel_spec(policy: dict):
    """The cell's ``policy`` block (``mesh``, ``wire``) as a ParallelSpec."""
    from repro.core.parallel import spec_from_cli
    return spec_from_cli(policy["mesh"], policy["wire"])


def _mesh(spec):
    from repro.launch.mesh import make_3d_mesh
    return make_3d_mesh(spec.dp, spec.stages, spec.tp)


def sharded_weights(conf: dict, seed: int, mesh):
    """bench/weights.py's weights for ``seed``, made in place on the
    mesh (the same values as ``weights.make``)."""
    from repro.launch.mesh import train_state_shardings
    frozen = weights.freeze(conf)

    def make(k):
        return weights._make(frozen, k, jnp.bfloat16)
    k = weights.key(seed)
    out = train_state_shardings(jax.eval_shape(make, k), mesh)
    return jax.jit(make, out_shardings=out)(k)


def build(ctx):
    """The compiled step, its state and the batch pool, on the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.policy import NO_POLICY
    from repro.launch.mesh import train_state_shardings
    from repro.optim.optimizers import OptimizerConfig, init_opt_state
    from repro.train.steps import make_lm_train_step

    cell, conf = ctx.cell, ctx.conf
    cfg = model_config(conf, ctx.conf_name)
    spec = parallel_spec(cell["policy"])
    mesh = _mesh(spec)
    opt = OptimizerConfig(kind="adamw", **cell["optimizer"])
    b, s = ctx.mix["batch"], ctx.mix["seq"]
    params = sharded_weights(conf, ctx.seed, mesh)
    init = lambda p: init_opt_state(opt, p)
    opt_state = jax.jit(init, out_shardings=train_state_shardings(
        jax.eval_shape(init, params), mesh))(params)
    step = make_lm_train_step(
        cfg, NO_POLICY, opt, remat=cell.get("remat", True), donate=True,
        parallel=spec, mesh=mesh,
        pipeline_microbatches=cell["policy"]["microbatches"])
    pool = traffic.markov2_pool(ctx.mix, cfg.vocab_size, b, s, ctx.seed)
    rep = NamedSharding(mesh, P())
    feed = [{"tokens": jax.device_put(t, rep)} for t in pool]
    ids = jax.device_put(jnp.arange(b, dtype=jnp.int32), rep)
    ctx.layer["mesh"] = mesh
    return step, [params, opt_state, []], feed, ids, pool


def first_steps(ctx, one, state, n: int) -> dict:
    """train_sim.first_steps with the weights' starting point made on the
    mesh, beside the state, rather than on one chip."""
    b1 = ctx.cell["optimizer"].get("beta1", 0.9)
    losses = []
    for i in range(n):
        losses.append(one(i))
        if i == 0:
            grad_norms = np.asarray(_sim._leaf_norms(
                jax.tree.map(lambda m: m / (1.0 - b1), state[1]["mu"])))
    p0 = sharded_weights(ctx.conf, ctx.seed, ctx.layer["mesh"])
    change_norms = np.asarray(_sim._change_norms(state[0], p0))
    del p0
    return {"losses": [float(x) for x in losses], "grad_norms": grad_norms,
            "change_norms": change_norms}


def _dispatch(one, i, n=None, deadline=None, span=False):
    """Steps back to back from step ``i``, at most two in flight, until
    ``n`` steps or the deadline; returns (next i, their losses).  With
    ``span`` each dispatch and wait is a ``bench.*`` host span."""
    mark = (jax.profiler.TraceAnnotation if span
            else lambda _: contextlib.nullcontext())
    inflight, losses = collections.deque(), []
    while True:
        with mark("bench.dispatch"):
            loss = one(i)
        i += 1
        inflight.append(loss)
        losses.append(loss)
        if len(inflight) > 2:
            with mark("bench.wait"):
                inflight.popleft().block_until_ready()
        if len(losses) == n or (deadline and time.perf_counter() >= deadline):
            break
    with mark("bench.wait"):
        jax.block_until_ready(list(inflight))
    return i, losses


def _traced_window(ctx, one, i, tokens_per_step) -> int:
    n = int(ctx.cell.get("trace_steps", 4))
    jax.block_until_ready(one(i))
    i += 1
    jax.profiler.start_trace(ctx.trace_dir)
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        i, losses = _dispatch(one, i, n=n, span=True)
    jax.profiler.stop_trace()
    tr = tracing.collect(ctx.trace_dir)
    mesh = ctx.layer["mesh"]
    cfg = model_config(ctx.conf, ctx.conf_name)
    ctx.layer.update({
        "trace": tr, "losses": losses, "steps": n,
        "busy_s": tracing.mean_busy_s(tr),
        "window_s": tracing.window_ns(tr) / 1e9,
        "breakdown": tracing.breakdown(tr),
        "model_flops": n * tokens_per_step * flops.train_flops_per_token(
            ctx.conf, ctx.mix["seq"]),
        # the devices of each pipeline stage, by their trace plane ids
        "stage_devices": [[str(d.id) for d in row.ravel()]
                          for row in mesh.devices[0]],
        "mesh_wire": mesh_wire_bytes(
            cfg, parallel_spec(ctx.cell["policy"]), ctx.mix["batch"],
            ctx.mix["seq"],
            microbatches=ctx.cell["policy"]["microbatches"],
            remat=ctx.cell.get("remat", True)),
    })
    return i


def run(ctx) -> dict:
    step, state, feed, ids, pool = build(ctx)
    harness.phase(ctx, "built")
    one = stepper(step, state, feed, ids)
    n_check = int(ctx.cell["check_steps"])
    prog = first_steps(ctx, one, state, n_check)
    harness.phase(ctx, "first steps")
    tokens_per_step = ctx.mix["batch"] * ctx.mix["seq"]
    if ctx.trace:
        _traced_window(ctx, one, n_check, tokens_per_step)
        e2e, window_losses = {}, ctx.layer.pop("losses")
    else:
        setup_s = time.perf_counter() - ctx.t0
        t_start = time.perf_counter()
        _, window_losses = _dispatch(one, n_check,
                                     deadline=t_start + ctx.seconds)
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t_start
        e2e = {"setup_s": setup_s,
               "train_tokens_per_s": len(window_losses) * tokens_per_step
               / elapsed}
    peak = harness.memory_peak_bytes(ctx.devices)
    e2e["peak_hbm_gib"] = peak / 2**30
    window_losses = [float(x) for x in window_losses]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    state.clear()
    del feed, one, step
    gc.collect()

    harness.phase(ctx, "window")
    ref = reference(ctx, pool[:n_check])
    harness.phase(ctx, "reference")
    g = gaps(prog, ref)
    checks = [harness.Check(k, g[k], float(v))
              for k, v in ctx.cell["limits"].items()]
    return {"e2e": e2e, "checks": checks, "correct": failed == 0,
            "attempted": len(window_losses), "failed": failed,
            "memory_peak_bytes": peak}
