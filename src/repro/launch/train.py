"""End-to-end training driver.

Trains any registry architecture (full or --smoke reduced variant) on the
synthetic LM stream with a boundary-compression policy, on the current
device set (CPU here; the same program lowers to the production mesh via
launch/dryrun.py).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --smoke \
      --steps 200 --batch 8 --seq 128 --policy top10reuse
  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --smoke \
      --steps 50 --policy q4q8 --grad-accum 2 --ckpt /tmp/mix.npz
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --smoke \
      --steps 50 --policy q4q8 --transport pipeline --stages 2
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --smoke \
      --steps 50 --transport pipeline --stages 2 --schedule 1f1b \
      --pipeline-microbatches 16
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --smoke \
      --steps 50 --policy q4q8 --transport pipeline --stages 2 \
      --schedule interleaved --virtual-stages 2
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --smoke \
      --steps 50 --mesh data=2,tensor=2 --wire data=q8,tensor=q8+ef:0.1
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --smoke \
      --steps 20 --mesh data=2,stage=2,tensor=2 --wire stage=q8,tensor=q4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import io as ckpt_io
from repro.configs.registry import ARCHS, get
from repro.obs import trace as obs_trace
from repro.core.boundary import init_boundary_state
from repro.core.parallel import spec_from_cli
from repro.launch.compile_cache import enable_compile_cache
from repro.core.policy import (CompressionPolicy, NO_POLICY, PolicyRules,
                               aqsgd_policy, ef_policy, parse_policy_rules,
                               quant_policy, resolve_policy, topk_policy)
from repro.models import encdec, transformer
from repro.models.config import active_param_count, param_count
from repro.optim.optimizers import OptimizerConfig, init_opt_state
from repro.train.steps import _resolve_parallel, make_lm_train_step

POLICIES = {
    "none": lambda: NO_POLICY,
    "q4q8": lambda: CompressionPolicy(num_stages=4,
                                      boundary=quant_policy(4, 8)),
    "top10": lambda: CompressionPolicy(num_stages=4,
                                       boundary=topk_policy(0.10)),
    "top10reuse": lambda: CompressionPolicy(
        num_stages=4, boundary=topk_policy(0.10, reuse_indices=True)),
    "ef21top10": lambda: CompressionPolicy(num_stages=4,
                                           boundary=ef_policy(0.10, "ef21")),
}


def synthetic_stream(cfg, batch: int, seq: int, seed: int = 0,
                     num_samples: int = 4096, start_step: int = 0,
                     dp: int = 1):
    """Deterministic order-2 Markov token stream (see data/synthetic.py),
    vocab-clipped to the model's vocabulary.  Each step's batch is a pure
    function of (seed, step), so ``start_step`` fast-forwards the stream —
    a resumed run sees exactly the batches the interrupted run would have.

    ``dp > 1`` deals ids per replica: contiguous batch shard r cycles over
    its own id block ``[r*num_samples/dp, (r+1)*num_samples/dp)`` — the
    AQ-SGD dp routing contract (each replica owns the buffer rows of the
    examples it sees; see ``repro.core.feedback.shard_ids``)."""
    rng = np.random.RandomState(seed)
    vocab = min(cfg.vocab_size, 1024)
    succ = rng.randint(0, vocab, size=(vocab, vocab, 4))
    step = start_step
    while True:
        r = np.random.RandomState(seed + 1 + step)
        out = np.zeros((batch, seq), np.int32)
        out[:, 0] = r.randint(0, vocab, batch)
        out[:, 1] = r.randint(0, vocab, batch)
        for t in range(2, seq):
            out[:, t] = succ[out[:, t - 2], out[:, t - 1],
                             r.randint(0, 4, batch)]
        # ids cycle over a bounded "dataset" so AQ-SGD's per-example
        # buffers revisit rows (the premise of the compensation)
        if dp > 1:
            sh, per = batch // dp, num_samples // dp
            ids = np.concatenate(
                [r * per + (np.arange(sh, dtype=np.int32) + sh * step) % per
                 for r in range(dp)])
        else:
            ids = (np.arange(batch, dtype=np.int32)
                   + batch * step) % num_samples
        yield out, ids
        step += 1


def make_batch(cfg, tokens):
    b = {"tokens": jnp.asarray(tokens)}
    n = tokens.shape[0]
    if cfg.frontend == "vision":
        b["patch_embeds"] = jnp.zeros((n, cfg.num_patches, cfg.d_model),
                                      jnp.bfloat16)
    if cfg.enc_dec:
        b["enc_embeds"] = jnp.zeros((n, cfg.enc_seq, cfg.d_model),
                                    jnp.bfloat16)
    return b


def adamw_config(lr: float, steps: int) -> OptimizerConfig:
    """The trainer's optimizer: AdamW with a cosine decay over ``steps``."""
    return OptimizerConfig(kind="adamw", lr=lr, weight_decay=0.01,
                           schedule="cosine", t_max=steps, grad_clip=1.0)


def init_bstates(cfg, policy, transport: str, *, seq: int, batch: int,
                 num_samples: int = 4096, microbatches=None,
                 virtual_stages: int = 1, dp: int = 1):
    """The boundary feedback state the train step threads, for the
    EFFECTIVE ``(policy, transport)`` that ``_resolve_parallel`` returns."""
    if transport == "pipeline":
        from repro.train.loop import _pipeline_bstates
        return _pipeline_bstates(
            policy, (seq, cfg.d_model), batch=batch,
            microbatches=microbatches, num_samples=num_samples,
            dtype=jnp.bfloat16, virtual_stages=virtual_stages, dp=dp)
    # boundaries that actually exist in the stack: segment_bounds caps
    # the stage count at the group count (a 2-group smoke model under a
    # 4-stage policy has 1 cut, not 3) — and the train step returns
    # bstates in that effective structure, which --resume restores into
    from repro.models.transformer import segment_bounds
    n_units = cfg.num_layers if cfg.enc_dec else cfg.num_groups
    eff = max(0, len(segment_bounds(n_units, policy.num_stages)) - 1)
    return [init_boundary_state(policy.at(i), (seq, cfg.d_model),
                                batch=batch, num_samples=num_samples,
                                dtype=jnp.bfloat16)
            for i in range(eff)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--policy", default="none",
                    help="a named policy (%s) OR an adaptive rule spec: "
                         "';'-separated 'codec[:k_frac][@cond,...]' rules, "
                         "conds size>=N | size<N | depth>=N | depth<N | "
                         "bandwidth>=X | bandwidth<X (bytes/s; fires only "
                         "under a probe — see obs/probes.py) | "
                         "dir=fw|bw — first match wins per boundary, e.g. "
                         "'q4@size>=65536;q8@size>=16384;none' (resolved "
                         "against seq*d_model at trace time)"
                         % ", ".join(sorted(POLICIES)))
    ap.add_argument("--transport", default="simulated",
                    choices=("simulated", "pipeline"),
                    help="simulated boundary (paper) or the real "
                         "compressed shard_map/ppermute pipeline")
    ap.add_argument("--stages", type=int, default=None,
                    help="pipeline stage count (default: policy's)")
    ap.add_argument("--schedule", default="gpipe",
                    choices=("gpipe", "1f1b", "interleaved"),
                    help="pipeline schedule: gpipe (minimum-tick skew "
                         "scan), 1f1b (rematerialized ticks + fused "
                         "single-buffer hops; use with "
                         "--pipeline-microbatches >> stages), interleaved "
                         "(--virtual-stages slices per device: 1/v the "
                         "bubble, v*S-1 compressed cuts)")
    ap.add_argument("--virtual-stages", type=int, default=None,
                    help="virtual stage slices per device for "
                         "--schedule interleaved (default 2)")
    ap.add_argument("--pipeline-microbatches", type=int, default=None,
                    help="GPipe/1F1B microbatch count for the pipeline "
                         "transport (default: the stage count)")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="3D mesh sizes, 'data=2,stage=2,tensor=2' (axis "
                         "aliases dp/pp/tp/model accepted; missing axes "
                         "default to 1).  stage>1 implies --transport "
                         "pipeline; tensor>1 shards the layer stack over "
                         "the compressed TP collectives "
                         "(transport/tp_collectives.py).  Replaces "
                         "--dp/--stages")
    ap.add_argument("--wire", default=None, metavar="SPEC",
                    help="per-axis wire config "
                         "'axis=codec[+feedback][:k_frac]', e.g. "
                         "'data=q8+ef:0.1,tensor=q4'.  Codecs "
                         "none|q8|q4|topk (or a quoted rule spec); "
                         "feedback ef|ef21.  Replaces --dp-codec/"
                         "--dp-feedback/--dp-k-frac")
    ap.add_argument("--dp", type=int, default=1,
                    help="DEPRECATED (use --mesh data=N): data-parallel "
                         "replicas: the global batch splits into --dp "
                         "contiguous shards and per-replica gradients are "
                         "all-reduced over the real wire "
                         "(transport/collectives.py).  With --transport "
                         "pipeline this runs the 2D (data, stages) mesh "
                         "(needs dp*stages host devices)")
    ap.add_argument("--dp-codec", default="none",
                    choices=("none", "q8", "q4", "topk"),
                    help="DEPRECATED (use --wire data=CODEC): wire codec "
                         "for the DP gradient all-reduce (paper Tables "
                         "2-3: gradients tolerate milder rates than "
                         "activations)")
    ap.add_argument("--dp-feedback", default="none",
                    choices=("none", "ef", "ef21"),
                    help="DEPRECATED (use --wire data=codec+FEEDBACK): "
                         "per-replica error feedback on the DP reduce "
                         "(residuals ride the train state and the "
                         "checkpoint)")
    ap.add_argument("--dp-k-frac", type=float, default=0.1,
                    help="DEPRECATED (use --wire data=topk:K): TopK kept "
                         "fraction for --dp-codec topk")
    ap.add_argument("--feedback", default="none",
                    choices=("none", "ef", "ef21", "efmixed", "aqsgd"),
                    help="error-feedback mode (paper Tables 3-4); replaces "
                         "the boundary with TopK(--k-frac) + this "
                         "compensation, on either transport")
    ap.add_argument("--k-frac", type=float, default=0.1,
                    help="TopK kept fraction for --feedback boundaries")
    ap.add_argument("--num-samples", type=int, default=4096,
                    help="AQ-SGD per-example buffer size; the synthetic "
                         "stream's ids cycle modulo this")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="gradient-accumulation splits of the global batch "
                         "(bounds activation memory at B/grad_accum)")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="DEPRECATED alias for --grad-accum (and, with "
                         "--transport pipeline, for "
                         "--pipeline-microbatches)")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint path (npz); saves the FULL train "
                         "state: params + optimizer moments + feedback "
                         "buffers (checkpoint/io.save_train_state).  A "
                         "'{step}' placeholder keeps one file per save "
                         "instead of overwriting")
    ap.add_argument("--save-every", type=int, default=None,
                    help="checkpoint every N steps (default 100)")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="DEPRECATED alias for --save-every")
    ap.add_argument("--resume", default=None,
                    help="resume from a --ckpt train-state file: restores "
                         "params, optimizer state, feedback buffers, and "
                         "the data-stream position")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, help="write metrics here")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry and write the JSONL event log "
                         "here (obs/export.py schema; default: tracing "
                         "off, zero overhead)")
    ap.add_argument("--perfetto", default=None, metavar="PATH",
                    help="also write a Chrome-trace JSON loadable at "
                         "ui.perfetto.dev / chrome://tracing")
    ap.add_argument("--metrics", type=int, default=0, metavar="N",
                    help="sample per-boundary compression error + "
                         "feedback-buffer norms every N steps (obs/"
                         "quality.py; 0 = off; implies tracing)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tracing = bool(args.trace or args.perfetto or args.metrics)
    if tracing:
        obs_trace.enable()

    cfg = get(args.arch, smoke=args.smoke)
    seq = min(args.seq, cfg.max_seq)
    save_every = args.save_every
    if args.ckpt_every is not None:
        import warnings
        if save_every is not None:
            ap.error("--ckpt-every (deprecated) conflicts with "
                     "--save-every — drop --ckpt-every")
        warnings.warn("--ckpt-every is deprecated: use --save-every",
                      DeprecationWarning)
        save_every = args.ckpt_every
    save_every = 100 if save_every is None else save_every
    grad_accum = args.grad_accum
    pipeline_mb = args.pipeline_microbatches
    if args.microbatches is not None:
        import warnings
        if args.transport == "pipeline":
            if pipeline_mb is not None:
                ap.error("--microbatches (deprecated) conflicts with "
                         "--pipeline-microbatches — drop --microbatches")
            warnings.warn("--microbatches is deprecated: use "
                          "--pipeline-microbatches for the pipeline "
                          "microbatch count", DeprecationWarning)
            if args.microbatches > 1:
                pipeline_mb = args.microbatches
        else:
            if grad_accum != 1:
                ap.error("--microbatches (deprecated) conflicts with "
                         "--grad-accum — drop --microbatches")
            warnings.warn("--microbatches is deprecated: use --grad-accum "
                          "for gradient accumulation", DeprecationWarning)
            grad_accum = args.microbatches
    virtual_stages = (args.virtual_stages if args.virtual_stages is not None
                      else (2 if args.schedule == "interleaved" else 1))
    if args.policy in POLICIES:
        policy = POLICIES[args.policy]()
    else:
        try:
            policy = parse_policy_rules(args.policy)
        except ValueError as e:
            ap.error(f"--policy {args.policy!r} is neither a named policy "
                     f"({', '.join(sorted(POLICIES))}) nor a valid rule "
                     f"spec: {e}")
    if args.feedback != "none":
        bp = (aqsgd_policy(args.k_frac) if args.feedback == "aqsgd"
              else ef_policy(args.k_frac, args.feedback))
        stages = policy.num_stages if policy.num_boundaries else 4
        policy = CompressionPolicy(num_stages=stages, boundary=bp)
    if args.stages:
        policy = dataclasses.replace(policy, num_stages=args.stages)
    if isinstance(policy, PolicyRules):
        # static resolution: rules -> concrete per-boundary codecs, keyed
        # by the LM's uniform cut size (hashable before any jit tracing)
        policy = resolve_policy(policy, seq * cfg.d_model)
    parallel = None
    if args.mesh or args.wire:
        legacy_used = [f for f, used in
                       (("--dp", args.dp != 1),
                        ("--dp-codec", args.dp_codec != "none"),
                        ("--dp-feedback", args.dp_feedback != "none"),
                        ("--dp-k-frac", args.dp_k_frac != 0.1),
                        ("--stages", bool(args.stages))) if used]
        if legacy_used:
            ap.error(f"--mesh/--wire conflict with the deprecated "
                     f"{', '.join(legacy_used)} — configure every axis "
                     "through --mesh/--wire")
        try:
            parallel = spec_from_cli(args.mesh, args.wire)
            # rule-coded axis wires resolve statically here (no probe on
            # this driver): data carries the gradient tree, stage/tensor
            # the per-example activation cut
            parallel = parallel.resolved(
                {"data": param_count(cfg), "stage": seq * cfg.d_model,
                 "tensor": seq * cfg.d_model // max(parallel.tp, 1)})
        except ValueError as e:
            ap.error(f"--mesh/--wire: {e}")
    if parallel is not None:
        spec_eff, policy_eff, transport_eff = _resolve_parallel(
            "launch.train", parallel, policy, args.transport, {})
    else:
        spec_eff, policy_eff, transport_eff = None, policy, args.transport
    dp_n = spec_eff.dp if spec_eff is not None else args.dp
    tp_n = spec_eff.tp if spec_eff is not None else 1
    need_devices = (spec_eff.num_devices if spec_eff is not None else
                    (args.dp * policy.num_stages
                     if args.transport == "pipeline" else args.dp))
    if (need_devices > 1
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # Must land before first jax backend init (imports alone are fine).
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={need_devices}")
    n_params = param_count(cfg)
    print(f"# arch={cfg.arch_id} params~{n_params/1e6:.1f}M "
          f"(active {active_param_count(cfg)/1e6:.1f}M) "
          f"B={args.batch} S={seq} policy={args.policy}"
          f"{'' if args.feedback == 'none' else '+' + args.feedback} "
          f"devices={jax.device_count()}", flush=True)

    opt = adamw_config(args.lr, args.steps)
    params = (encdec if cfg.enc_dec else transformer).init_params(
        jax.random.PRNGKey(args.seed), cfg)
    opt_state = init_opt_state(opt, params)
    bstates = init_bstates(cfg, policy_eff, transport_eff, seq=seq,
                           batch=args.batch, num_samples=args.num_samples,
                           microbatches=pipeline_mb,
                           virtual_stages=virtual_stages, dp=dp_n)
    if transport_eff == "pipeline":
        from repro.transport.schedules import get_schedule
        sched = get_schedule(args.schedule, virtual_stages)
        mb_eff = pipeline_mb or policy_eff.num_stages
        print(f"# pipeline transport: schedule={args.schedule} "
              f"microbatches={mb_eff} "
              f"{sched.describe(mb_eff, policy_eff.num_stages)}", flush=True)
    pkw = {}
    if parallel is not None:
        pkw["parallel"] = parallel
    else:
        # only forward the legacy kwargs the user actually set, so a
        # plain run never trips the ParallelDeprecationWarning
        if args.dp != 1:
            pkw["dp"] = args.dp
        if args.dp_codec != "none":
            pkw["dp_codec"] = args.dp_codec
        if args.dp_feedback != "none":
            pkw["dp_feedback"] = args.dp_feedback
        if args.dp_k_frac != 0.1:
            pkw["dp_k_frac"] = args.dp_k_frac
    step_fn = make_lm_train_step(cfg, policy, opt, remat=not args.no_remat,
                                 donate=False,
                                 grad_accum=grad_accum,
                                 transport=args.transport,
                                 pipeline_microbatches=pipeline_mb,
                                 schedule=args.schedule,
                                 virtual_stages=virtual_stages, **pkw)
    dp_codec_eff = (spec_eff.data.codec if spec_eff is not None
                    else args.dp_codec)
    dp_feedback_eff = (spec_eff.data.feedback if spec_eff is not None
                       else args.dp_feedback)
    dp_state = None
    if dp_n > 1:
        from repro.train.loop import init_lm_dp_state
        dp_state = init_lm_dp_state(cfg, params, policy_eff, dp_n,
                                    dp_feedback_eff,
                                    transport=transport_eff,
                                    virtual_stages=virtual_stages, tp=tp_n)
        print(f"# dp={dp_n} gradient all-reduce: codec={dp_codec_eff} "
              f"feedback={dp_feedback_eff}", flush=True)
    tp_state = None
    if tp_n > 1:
        t_ax = spec_eff.tensor
        print(f"# tp={tp_n} tensor collectives: codec={t_ax.codec} "
              f"feedback={t_ax.feedback}", flush=True)
        if transport_eff == "simulated":
            from repro.models.transformer import tp_sites
            from repro.transport.tp_collectives import init_tp_state
            tp_state = init_tp_state((args.batch, seq, cfg.d_model),
                                     tp_sites(cfg), t_ax.feedback)

    start_step = 0
    if args.resume:
        if dp_n > 1:
            params, opt_state, bstates, dp_state, start_step = \
                ckpt_io.restore_train_state(args.resume, params, opt_state,
                                            bstates, dp_like=dp_state)
        else:
            params, opt_state, bstates, start_step = \
                ckpt_io.restore_train_state(args.resume, params, opt_state,
                                            bstates)
        print(f"# resumed step-{start_step} train state from {args.resume}",
              flush=True)
        if tp_state is not None and spec_eff.tensor.feedback != "none":
            print("# note: tensor-wire feedback residuals are not "
                  "checkpointed — resuming with zeroed tp_state", flush=True)
    stream = synthetic_stream(cfg, args.batch, seq, args.seed,
                              num_samples=args.num_samples,
                              start_step=start_step, dp=dp_n)
    tap = None
    if args.metrics:
        from repro.obs.quality import QualityTap
        tap = QualityTap((args.batch, seq, cfg.d_model),
                         every=args.metrics, dtype=jnp.bfloat16,
                         seed=args.seed)
    metrics, t0 = [], time.time()
    tokens_per_step = args.batch * seq
    for step in range(start_step + 1, args.steps + 1):
        toks, ids = next(stream)
        with obs_trace.span("train.step", cat="train", step=step) as sa:
            extra = [s for s in (dp_state, tp_state) if s is not None]
            out = step_fn(params, opt_state, bstates, make_batch(cfg, toks),
                          jnp.asarray(ids), *extra)
            params, opt_state, bstates, m = out[0], out[1], out[2], out[-1]
            rest = list(out[3:-1])
            if dp_state is not None:
                dp_state = rest.pop(0)
            if tp_state is not None:
                tp_state = rest.pop(0)
            if tracing:
                sa["loss"] = round(float(m["loss"]), 6)  # sync in span
        if tap is not None:
            tap.maybe_sample(step, policy, bstates or None)
        if step % args.log_every == 0 or step == args.steps:
            dt = time.time() - t0
            loss = float(m["loss"])
            rec = {"step": step, "loss": round(loss, 4),
                   "ppl": round(math.exp(min(loss, 20.0)), 2),
                   "tok_per_s": round((step - start_step) * tokens_per_step
                                      / dt, 1),
                   "wall_s": round(dt, 1)}
            metrics.append(rec)
            print(json.dumps(rec), flush=True)
        if args.ckpt and (step % save_every == 0 or step == args.steps):
            ckpt_io.save_train_state(
                args.ckpt.replace("{step}", str(step)), params, opt_state,
                bstates, step=step,
                extra={"arch": cfg.arch_id, "policy": args.policy,
                       "feedback": args.feedback, "dp": dp_n,
                       "dp_codec": dp_codec_eff, "tp": tp_n},
                dp_state=dp_state)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(metrics, f, indent=1)
    if tracing:
        tr = obs_trace.get_tracer()
        events = tr.drain()
        if args.trace:
            from repro.obs.export import to_jsonl
            print(f"# trace: {to_jsonl(events, args.trace)} events "
                  f"-> {args.trace} (dropped {tr.dropped})", flush=True)
        if args.perfetto:
            from repro.obs.export import to_chrome_trace
            print(f"# perfetto: {to_chrome_trace(events, args.perfetto)} "
                  f"events -> {args.perfetto}", flush=True)
    print("# done: final loss "
          f"{metrics[-1]['loss'] if metrics else 'n/a (already at --steps)'}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
