"""Training cells on the simulated transport (one chip).

The timed path is the repo's trainer: ``repro.train.steps.
make_lm_train_step`` with ``transport="simulated"`` (the paper's stage
cuts inside one program, their codecs on the TPU's Pallas kernels), AdamW
from ``repro.optim``, donated state and rematerialised layers.

Set-up builds that one step and its state from the seed, and drives it
through the cell's ``check_steps`` first steps on distinct batches, with
the window's own call and feed.  From those steps it keeps each loss, the
first gradient as the optimizer got it (AdamW's first moment after step 1
is (1 - beta1) times the clipped gradient) and the weights' change over
those steps, each as per-leaf norms on the device.  The window then
dispatches steps back to back (at most two in flight) for ``--seconds``;
losses are read only after it.  Once the window has closed, the memory
peak has been read and the program's state freed, the plain reference
(bench/reference/) follows the same first steps and the gaps are
compared with the cell's limits.
"""
from __future__ import annotations

import collections
import gc
import importlib
import math
import time

import numpy as np
import jax
import jax.numpy as jnp

from bench import flops, harness, traffic, weights
from bench import trace as tracing
from bench.configs import model_config, sizes
from bench.reference.dense_lm import leaf_items, segment_cuts


def policy_of(spec: dict):
    """The cell's ``policy`` block as the program's CompressionPolicy."""
    from repro.core.compressors import Compressor
    from repro.core.policy import BoundaryPolicy, CompressionPolicy

    def comp(c):
        if c[0] == "none":
            return Compressor("none")
        if c[0] == "quant":
            return Compressor("quant", bits=int(c[1]))
        return Compressor("topk", k_frac=float(c[1]))

    return CompressionPolicy(
        num_stages=int(spec["num_stages"]),
        boundary=BoundaryPolicy(fw=comp(spec["fw"]), bw=comp(spec["bw"])))


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for _, a in leaf_items(tree)])


@jax.jit
def _change_norms(new, old):
    diff = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                        - b.astype(jnp.float32), new, old)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a)))
                      for _, a in leaf_items(diff)])


def build(ctx):
    """The compiled step, its state and the batch pool, on the device."""
    from repro.core.boundary import init_boundary_state
    from repro.models.transformer import segment_bounds
    from repro.optim.optimizers import OptimizerConfig, init_opt_state
    from repro.train.steps import make_lm_train_step

    cell, conf = ctx.cell, ctx.conf
    cfg = model_config(conf, ctx.conf_name)
    policy = policy_of(cell["policy"])
    opt = OptimizerConfig(kind="adamw", **cell["optimizer"])
    b, s = ctx.mix["batch"], ctx.mix["seq"]
    params = weights.make(conf, ctx.seed)
    opt_state = jax.jit(lambda p: init_opt_state(opt, p))(params)
    cuts = len(segment_bounds(cfg.num_groups, policy.num_stages)) - 1
    bstates = [init_boundary_state(policy.at(i), (s, cfg.d_model), batch=b,
                                   dtype=jnp.bfloat16) for i in range(cuts)]
    step = make_lm_train_step(cfg, policy, opt, remat=cell.get("remat", True),
                              donate=True)
    pool = traffic.markov2_pool(ctx.mix, cfg.vocab_size, b, s, ctx.seed)
    feed = [{"tokens": jax.device_put(t)} for t in pool]
    ids = jnp.arange(b, dtype=jnp.int32)
    return step, [params, opt_state, bstates], feed, ids, pool


def stepper(step, state, feed, ids):
    """``one(i)``: step ``i`` on the pool's batch ``i``, state carried."""
    def one(i):
        params, opt_state, bstates, m = step(*state, feed[i % len(feed)],
                                             ids)
        state[:] = [params, opt_state, bstates]
        return m["loss"]
    return one


def first_steps(ctx, one, state, n: int) -> dict:
    """Drive the first ``n`` steps and read what the check compares: each
    loss, the per-leaf norms of the first gradient as the optimizer got
    it, and of the weights' change over the ``n`` steps."""
    b1 = ctx.cell["optimizer"].get("beta1", 0.9)
    losses = []
    for i in range(n):
        losses.append(one(i))
        if i == 0:
            grad_norms = np.asarray(_leaf_norms(
                jax.tree.map(lambda m: m / (1.0 - b1), state[1]["mu"])))
    p0 = weights.make(ctx.conf, ctx.seed)
    change_norms = np.asarray(_change_norms(state[0], p0))
    del p0
    return {"losses": [float(x) for x in losses], "grad_norms": grad_norms,
            "change_norms": change_norms}


def run(ctx) -> dict:
    step, state, feed, ids, pool = build(ctx)
    harness.phase(ctx, "built")
    one = stepper(step, state, feed, ids)
    n_check = int(ctx.cell["check_steps"])
    prog = first_steps(ctx, one, state, n_check)
    harness.phase(ctx, "first steps")
    tokens_per_step = ctx.mix["batch"] * ctx.mix["seq"]
    i = n_check

    if ctx.trace:
        i = _traced_window(ctx, one, i, tokens_per_step)
        e2e, window_losses = {}, ctx.layer.pop("losses")
    else:
        setup_s = time.perf_counter() - ctx.t0
        inflight, window_losses = collections.deque(), []
        t_start = time.perf_counter()
        deadline = t_start + ctx.seconds
        while True:
            loss = one(i)
            i += 1
            inflight.append(loss)
            window_losses.append(loss)
            if len(inflight) > 2:
                inflight.popleft().block_until_ready()
            if time.perf_counter() >= deadline:
                break
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
    del feed, one
    gc.collect()

    harness.phase(ctx, "window")
    ref = reference(ctx, pool[:n_check])
    harness.phase(ctx, "reference")
    # the cell's limits name the numbers it compares
    g = gaps(prog, ref)
    checks = [harness.Check(k, g[k], float(v))
              for k, v in ctx.cell["limits"].items()]
    return {"e2e": e2e, "checks": checks, "correct": failed == 0,
            "attempted": len(window_losses), "failed": failed,
            "memory_peak_bytes": peak}


def _traced_window(ctx, one, i, tokens_per_step) -> int:
    """A short steady window of steps under the profiler; leaves the
    reduced trace and the step count for the per-layer readers."""
    n = int(ctx.cell.get("trace_steps", 8))
    jax.block_until_ready(one(i))
    i += 1
    jax.profiler.start_trace(ctx.trace_dir)
    losses = []
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        inflight = collections.deque()
        for _ in range(n):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                loss = one(i)
            i += 1
            inflight.append(loss)
            losses.append(loss)
            if len(inflight) > 2:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    inflight.popleft().block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            for x in inflight:
                x.block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.collect(ctx.trace_dir)
    ctx.layer.update({
        "trace": tr, "losses": losses, "steps": n,
        "busy_s": tracing.mean_busy_s(tr),
        "window_s": tracing.window_ns(tr) / 1e9,
        "breakdown": tracing.breakdown(tr),
        "model_flops": n * tokens_per_step * flops.train_flops_per_token(
            ctx.conf, ctx.mix["seq"]),
    })
    # each cut runs one kernel forward and one on the gradient
    sz = sizes(ctx.conf)
    calls = 2 * len(segment_cuts(sz["num_layers"],
                                 ctx.cell["policy"]["num_stages"]))
    ctx.layer["wire_calls_per_step"] = calls
    ctx.layer["wire_bytes_per_step"] = calls * flops.boundary_kernel_bytes(
        ctx.mix["batch"], ctx.mix["seq"], sz["d_model"])
    return i


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers the check can compare: the largest relative loss gap
    over the check steps, and the gap between the program's and the
    reference's per-leaf norms of the first gradient and of the weights'
    change, each over the larger of the reference leaf's norm and the
    median leaf's, by the worst leaf (``grad_gap``, ``update_gap``) and by
    the median leaf (``*_median``).  Leaves whose reference gradient is
    under a thousandth of the median leaf's move by round-off alone and
    are left out of the change.  The cell's limits pick which are
    compared."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    gr, gp = np.asarray(ref["grad_norms"]), np.asarray(prog["grad_norms"])
    moved = gr >= 1e-3 * np.median(gr)
    cr, cp = (np.asarray(ref["change_norms"])[moved],
              np.asarray(prog["change_norms"])[moved])
    g = np.abs(gp - gr) / np.maximum(gr, np.median(gr))
    c = np.abs(cp - cr) / np.maximum(cr, np.median(cr))
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": float(np.max(g)), "update_gap": float(np.max(c)),
            "grad_gap_median": float(np.median(g)),
            "update_gap_median": float(np.median(c))}


def reference(ctx, batches, precision: str = "f32") -> dict:
    """The configuration's plain reference (``bench/reference/<name>.py``)
    over the same first steps."""
    ref = importlib.import_module(f"bench.reference.{ctx.conf['reference']}")
    cell = ctx.cell
    return ref.train_readings(ctx.conf, cell["policy"], cell["optimizer"],
                              list(batches), ctx.seed, precision)
