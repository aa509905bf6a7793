"""Train / eval steps wiring the paper's boundary compression into the
optimizer loop.

Two transports (see repro/transport/):

  * ``transport="simulated"`` — the paper's single-device boundary
    (core/boundary.py): the bw feedback buffers are updated inside
    backprop, so ``loss_fn`` takes them as a differentiated argument and
    the train step reads the update out of the gradient pytree.
  * ``transport="pipeline"``  — the REAL ``shard_map``/``ppermute``
    pipeline (transport/pipeline.py): packed payloads cross the wire in
    both directions; needs ``device_count >= policy.num_stages`` and a
    uniform per-cut policy (SPMD).  Feedback buffers (EF/EF21/EF-mixed/
    AQ-SGD) ride the pipeline scan carry: ``bstates`` is the
    ``init_feedback_state`` pytree ({"fw","bw"} of stage-stacked buffers)
    instead of the simulated per-boundary list; bw buffer updates are read
    out of the gradient w.r.t. ``bstates["bw"]``, mirroring the simulated
    path's cotangent trick.

Everything is jit-friendly and policy-static.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.parallel import ParallelSpec, from_legacy, warn_legacy
from repro.core.policy import CompressionPolicy, PolicyRules, resolve_policy
from repro.models import encdec, transformer
from repro.models.transformer import lm_loss
from repro.optim.optimizers import OptimizerConfig, apply_updates

# Sentinel distinguishing "caller passed the legacy kwarg" (deprecation
# shim -> ParallelSpec) from "default" on make_lm_train_step & friends.
_UNSET = object()

_LEGACY_DEFAULTS = {"dp": 1, "dp_codec": "none", "dp_feedback": "none",
                    "dp_k_frac": 0.1}


def _resolve_parallel(api: str, parallel, policy, transport: str, legacy):
    """Fold ``parallel=`` and the deprecated ``dp_*`` kwarg family into
    one ``(ParallelSpec, policy, transport)`` triple.

    Legacy kwargs (values ``_UNSET`` when not passed) construct the
    equivalent spec via :func:`repro.core.parallel.from_legacy` and warn
    once per call site; passing both families is an error.  A spec with
    ``stages > 1`` implies the pipeline transport; its stage wire
    (``spec.stage_policy()``) becomes the boundary policy unless the
    caller already supplied a compressing ``policy`` (conflict)."""
    explicit = tuple(sorted(k for k, v in legacy.items() if v is not _UNSET))
    if parallel is not None:
        if explicit:
            raise ValueError(
                f"{api}: both parallel= and the legacy kwarg(s) "
                f"{list(explicit)} were passed — drop the legacy kwargs")
        if not isinstance(parallel, ParallelSpec):
            raise TypeError(f"{api}: parallel= must be a ParallelSpec, "
                            f"got {type(parallel).__name__}")
        spec = parallel
    else:
        if explicit:
            warn_legacy(api, explicit)
        vals = {k: (legacy[k] if legacy[k] is not _UNSET else d)
                for k, d in _LEGACY_DEFAULTS.items()}
        spec = from_legacy(
            num_stages=(policy.num_stages if transport == "pipeline" else 1),
            **vals)
    for name in ("data", "stage", "tensor"):
        if spec.axis(name).is_rules:
            raise ValueError(
                f"{api}: the {name!r} axis codec is an unresolved rule "
                "spec — call ParallelSpec.resolved(wire_sizes, bandwidth) "
                "first (run_lm_experiment does this per epoch)")
    if parallel is not None and spec.stages > 1:
        if transport == "simulated":
            transport = "pipeline"
        sp = spec.stage_policy()
        if sp is not None:
            from repro.core.policy import NO_COMPRESSION
            if (policy.num_stages > 1 or policy.overrides
                    or policy.boundary != NO_COMPRESSION):
                raise ValueError(
                    f"{api}: both the stage axis wire "
                    f"({spec.stage.codec}+{spec.stage.feedback}) and a "
                    f"compressing policy= ({policy.name}) were given — "
                    "configure the stage boundary in ONE place")
            policy = sp
        elif policy.num_stages == 1:
            import dataclasses as _dc
            policy = _dc.replace(policy, num_stages=spec.stages)
        elif policy.num_stages != spec.stages:
            raise ValueError(
                f"{api}: policy.num_stages={policy.num_stages} != "
                f"parallel stage size {spec.stages}")
    return spec, policy, transport


def _resolve_rules(policy, boundary_feat):
    """Resolve a :class:`~repro.core.policy.PolicyRules` rule set into a
    concrete :class:`CompressionPolicy` at trace time.

    ``boundary_feat``: per-boundary tensor element count (one int for
    homogeneous cuts, or a sequence with one entry per cut).  Plain
    ``CompressionPolicy`` values pass through untouched, so a degenerate
    one-rule set reproduces a static-policy run bit-for-bit.
    """
    if isinstance(policy, PolicyRules):
        if boundary_feat is None:
            raise ValueError(
                "policy is a PolicyRules rule set — pass boundary_feat= "
                "(elements crossing each cut, e.g. seq_len * d_model for "
                "the LM) so rules can resolve to concrete codecs")
        return resolve_policy(policy, boundary_feat)
    return policy


def _uniform_boundary(policy: CompressionPolicy):
    """The single per-cut policy the SPMD pipeline runs at every cut."""
    from repro.core.policy import BoundaryPolicy
    if policy.num_boundaries == 0:
        return BoundaryPolicy()
    bps = [policy.at(i) for i in range(policy.num_boundaries)]
    if any(bp != bps[0] for bp in bps):
        raise ValueError("the SPMD pipeline transport needs the same "
                         "boundary policy at every cut (one program)")
    return bps[0]


def _pipeline_mesh(policy: CompressionPolicy, mesh, stage_axis: str):
    if mesh is not None:
        return mesh
    s = policy.num_stages
    if jax.device_count() < s:
        raise RuntimeError(
            f"pipeline transport needs >= {s} devices, have "
            f"{jax.device_count()} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={s} before jax init")
    from repro.launch.mesh import make_mesh
    return make_mesh((s,), (stage_axis,))


def _tp_stage_fn(cfg, mesh, tp, tp_codec, tp_k_frac, tensor_axis, remat):
    """Stage function + extra ``pipeline_apply`` kwargs for an optional
    tensor axis.  ``tp == 1`` returns the plain dense stage fn and no
    extra kwargs; ``tp > 1`` returns a TP-sharded stage fn (compressed
    all-gather / reduce-scatter per block, feedback-free) plus the
    ``tp_axis``/``tp_param_dims``/``seq_dim`` kwargs pipeline_apply needs
    to extend its shard_map specs over ``tensor_axis``."""
    if tp == 1:
        return transformer.stage_stack_fn(cfg, remat), lambda stack: {}
    from repro.transport.tp_collectives import TPCollectives
    tpc = TPCollectives(mesh, tensor_axis, codec=tp_codec,
                        k_frac=tp_k_frac, feedback="none")
    tp_fn = transformer.tp_stage_stack_fn(cfg, tpc, remat)

    def stage_fn(gp_stack, x):
        z = jnp.zeros((0,), x.dtype)
        return tp_fn(gp_stack, x, z, z)[0]

    def tp_kwargs(stack):
        return {"tp_axis": tensor_axis,
                "tp_param_dims": transformer.tp_param_dims(stack),
                "seq_dim": 1}

    return stage_fn, tp_kwargs


def _split_leading(tree, k: int):
    """Reshape every leaf ``(N, ...) -> (k, N/k, ...)``: the shard split
    shared by gradient accumulation (k chunks) and DP (k replica lanes)."""
    return jax.tree.map(
        lambda a: a.reshape(k, a.shape[0] // k, *a.shape[1:]), tree)


def _merge_leading(tree):
    """Inverse of :func:`_split_leading`."""
    return jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), tree)


def _split_states(bstates):
    fw = [s["fw"] for s in bstates]
    bw = [s["bw"] for s in bstates]
    return fw, bw


def _merge_states(fw, bw):
    return [{"fw": f, "bw": b} for f, b in zip(fw, bw)]


# ---------------------------------------------------------------------------
# LM train step (decoder-only + enc-dec)
# ---------------------------------------------------------------------------

def _resolve_grad_accum(grad_accum: int,
                        microbatches: Optional[int]) -> int:
    """``microbatches=`` is the deprecated name of the grad-accumulation
    knob (it collided with the pipeline's GPipe microbatch count)."""
    if microbatches is None:
        return grad_accum
    if grad_accum != 1:
        raise ValueError(
            f"both grad_accum={grad_accum} and its deprecated alias "
            f"microbatches={microbatches} were passed — drop microbatches=")
    warnings.warn(
        "microbatches= is deprecated (it means gradient accumulation, not "
        "pipeline microbatches): pass grad_accum= instead, and "
        "pipeline_microbatches= for the GPipe microbatch count",
        DeprecationWarning, stacklevel=3)
    return microbatches


def make_lm_train_step(cfg, policy: CompressionPolicy,
                       opt: OptimizerConfig, aux_weight: float = 0.01,
                       remat: bool = True, donate: bool = True,
                       jit: bool = True, grad_accum: int = 1,
                       microbatches: Optional[int] = None,
                       transport: str = "simulated", mesh=None,
                       stage_axis: str = "stage",
                       pipeline_microbatches: Optional[int] = None,
                       schedule: str = "gpipe", virtual_stages: int = 1,
                       dp=_UNSET, dp_codec=_UNSET,
                       dp_feedback=_UNSET, dp_k_frac=_UNSET,
                       data_axis: str = "data", boundary_feat=None,
                       parallel: Optional[ParallelSpec] = None,
                       tensor_axis: str = "tensor"):
    """Returns jit'd ``step(params, opt_state, bstates, batch, ids)
    -> (params, opt_state, bstates, metrics)``.

    batch: {"tokens": (B,S)} (+ modality stubs); next-token LM loss.
    ``grad_accum > 1``: gradient accumulation — the global batch is split
    along B and scanned, bounding per-device activation memory at
    B/grad_accum (feedback buffers and ids are sliced alongside, so the
    paper's per-example semantics are preserved).  ``microbatches=`` is a
    deprecated alias for ``grad_accum=``.

    ``transport="pipeline"`` trains through the real ``ppermute`` path:
    embed + loss run replicated, the layer stack runs as a compressed
    pipeline over ``mesh``'s ``stage_axis`` under ``schedule``
    (gpipe | 1f1b | interleaved; ``virtual_stages`` slices per device for
    interleaved; ``pipeline_microbatches`` defaults to the stage count).

    ``dp > 1`` adds a data-parallel dimension with a COMPRESSED gradient
    all-reduce (transport/collectives.py): the global batch splits into
    ``dp`` contiguous shards, per-replica gradients cross the ``data``
    mesh axis packed by ``dp_codec`` (none/q8/q4/topk at ``dp_k_frac``),
    optionally error-compensated per replica (``dp_feedback``:
    ef | ef21).  The step signature gains a DP-state argument:
    ``step(params, opt_state, bstates, batch, ids, dp_state)
    -> (params, opt_state, bstates, dp_state, metrics)`` with ``dp_state``
    from :func:`repro.transport.collectives.init_dp_state`.  On the
    simulated transport the replicas are ``vmap`` lanes around the paper's
    boundary (``grad_accum`` composes per lane — accumulate locally,
    reduce once); on the pipeline transport the mesh is the 2D
    ``(data, stages)`` grid and the reduced tree is the pipelined layer
    stack (embed/head/norm grads stay exact: they run replicated).

    ``parallel=`` (a :class:`~repro.core.parallel.ParallelSpec`) is the
    ONE argument that now configures all three axes — sizes and wires for
    ``data`` (the compressed gradient all-reduce), ``stage`` (the
    pipeline boundary; ``stages > 1`` implies the pipeline transport) and
    ``tensor`` (the compressed TP collectives,
    transport/tp_collectives.py).  The ``dp``/``dp_codec``/
    ``dp_feedback``/``dp_k_frac`` kwargs are a DEPRECATED alias family
    (they construct the equivalent spec and warn with
    ``ParallelDeprecationWarning``); passing both families is an error.

    ``tp > 1`` shards the dense-family layer stack over the tensor axis
    (Megatron-SP: sequence-sharded residual, head/d_ff-sharded weights)
    with the all-gather/reduce-scatter packed by the tensor wire codec.
    The step gains a trailing ``tp_state`` argument (from
    :func:`repro.transport.tp_collectives.init_tp_state`) and returns it
    updated: ``step(params, opt_state, bstates, batch, ids[, dp_state],
    tp_state)``.
    """
    mod = encdec if cfg.enc_dec else transformer
    policy = _resolve_rules(policy, boundary_feat)
    grad_accum = _resolve_grad_accum(grad_accum, microbatches)
    spec, policy, transport = _resolve_parallel(
        "make_lm_train_step", parallel, policy, transport,
        {"dp": dp, "dp_codec": dp_codec, "dp_feedback": dp_feedback,
         "dp_k_frac": dp_k_frac})
    dp, tp = spec.dp, spec.tp
    d_ax, t_ax = spec.data, spec.tensor
    dp_codec, dp_feedback, dp_k_frac = d_ax.codec, d_ax.feedback, d_ax.k_frac
    if transport == "pipeline":
        if grad_accum > 1:
            raise NotImplementedError(
                "grad_accum > 1 is not supported with transport='pipeline' "
                "— bound activation memory with pipeline_microbatches (the "
                "1f1b schedule keeps the stash at the boundary tensors)")
        return _make_pipeline_lm_train_step(
            cfg, policy, opt, mesh=mesh, stage_axis=stage_axis,
            microbatches=pipeline_microbatches, jit=jit, donate=donate,
            schedule=schedule, virtual_stages=virtual_stages,
            dp=dp, dp_codec=dp_codec, dp_feedback=dp_feedback,
            dp_k_frac=dp_k_frac, data_axis=data_axis, tp=tp,
            tp_codec=t_ax.codec, tp_k_frac=t_ax.k_frac,
            tp_feedback=t_ax.feedback, tensor_axis=tensor_axis, remat=remat)
    if transport != "simulated":
        raise ValueError(f"unknown transport {transport!r}")
    if tp > 1:
        if grad_accum > 1:
            raise NotImplementedError("grad_accum > 1 + tensor parallelism")
        return _make_tp_lm_train_step(
            cfg, policy, opt, mesh=mesh, jit=jit, dp=dp, tp=tp,
            dp_codec=dp_codec, dp_feedback=dp_feedback,
            dp_k_frac=dp_k_frac, data_axis=data_axis,
            tp_codec=t_ax.codec, tp_feedback=t_ax.feedback,
            tp_k_frac=t_ax.k_frac, tensor_axis=tensor_axis, remat=remat)

    def loss_fn(params, bw_bufs, fw_bufs, batch, ids):
        bstates = _merge_states(fw_bufs, bw_bufs)
        labels = jnp.roll(batch["tokens"], -1, axis=1)
        mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
        # chunked loss from hidden states: (B,S,V) logits never
        # materialized (see transformer.hidden_lm_loss) — both stacks
        x, aux, new_fw = mod.forward_hidden(
            params, batch, cfg, policy, bstates or None, ids,
            remat=remat)
        loss = transformer.hidden_lm_loss(params, x, labels, cfg, mask)
        total = loss + aux_weight * aux
        return total, (loss, aux, new_fw)

    def compute_grads(params, bw_bufs, fw_bufs, batch, ids):
        """One replica's (grads, new_fw, new_bw, metrics) over its batch
        shard; ``grad_accum`` scans within the shard, so accumulation
        composes with the DP reduce (accumulate locally, reduce once)."""
        if grad_accum == 1:
            (total, (loss, aux, new_fw)), (grads, new_bw) = \
                jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
                    params, bw_bufs, fw_bufs, batch, ids)
            return grads, new_fw, new_bw, {"loss": loss, "aux": aux,
                                           "total": total}
        mb = grad_accum
        split = lambda t: _split_leading(t, mb)
        xs = (split(batch), split(ids), split(fw_bufs), split(bw_bufs))
        grad0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(carry, xs_i):
            gacc, loss_a, aux_a = carry
            b_i, id_i, fw_i, bw_i = xs_i
            (_, (loss, aux, new_fw)), (g, new_bw) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    params, bw_i, fw_i, b_i, id_i)
            gacc = jax.tree.map(
                lambda a, gg: a + gg.astype(jnp.float32), gacc, g)
            return (gacc, loss_a + loss, aux_a + aux), (new_fw, new_bw)

        (gacc, loss_s, aux_s), (new_fw_s, new_bw_s) = jax.lax.scan(
            body, (grad0, jnp.float32(0.0), jnp.float32(0.0)), xs)
        grads = jax.tree.map(lambda g: (g / mb).astype(jnp.bfloat16), gacc)
        new_fw = [_merge_leading(b) for b in new_fw_s]
        new_bw = [_merge_leading(b) for b in new_bw_s]
        metrics = {"loss": loss_s / mb, "aux": aux_s / mb,
                   "total": (loss_s + aux_weight * aux_s) / mb}
        return grads, new_fw, new_bw, metrics

    if grad_accum > 1 and policy.num_boundaries and any(
            policy.at(i).feedback == "aqsgd"
            for i in range(policy.num_boundaries)):
        raise NotImplementedError("aqsgd + gradient accumulation")

    def step(params, opt_state, bstates, batch, ids):
        fw_bufs, bw_bufs = _split_states(bstates)
        grads, new_fw, new_bw, metrics = compute_grads(
            params, bw_bufs, fw_bufs, batch, ids)
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        new_states = _merge_states(new_fw if new_fw else fw_bufs, new_bw)
        return params, opt_state, new_states, metrics

    if dp > 1:
        step = _make_dp_simulated_step(policy, opt, compute_grads, dp,
                                       dp_codec, dp_feedback, dp_k_frac,
                                       data_axis)

    if not jit:
        return step
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def _make_dp_simulated_step(policy, opt, compute_grads, dp, dp_codec,
                            dp_feedback, dp_k_frac, data_axis):
    """Data-parallel wrapper around the simulated-boundary gradient
    computation: ``dp`` ``vmap`` lanes (one per contiguous batch shard),
    then one compressed all-reduce of the per-lane gradients over the
    ``data`` mesh axis.  Global feedback buffers split by batch shard;
    AQ-SGD's dataset-indexed ``(num_samples, *feat)`` buffer splits BY
    EXAMPLE ID (lane r owns rows ``[r*ns/dp, (r+1)*ns/dp)`` and addresses
    them with localized ids — :func:`repro.core.feedback.shard_ids`), so
    the per-example compensation never crosses lanes."""
    from repro.core.feedback import shard_ids
    from repro.launch.mesh import make_data_mesh
    from repro.transport.collectives import make_grad_all_reduce
    has_aqsgd = policy.num_boundaries and any(
        policy.at(i).feedback == "aqsgd"
        for i in range(policy.num_boundaries))
    mesh = make_data_mesh(dp, data_axis=data_axis)
    reduce_fn = make_grad_all_reduce(mesh, data_axis, dp_codec,
                                     k_frac=dp_k_frac,
                                     feedback=dp_feedback, average=True)

    def step_dp(params, opt_state, bstates, batch, ids, dp_state):
        fw_bufs, bw_bufs = _split_states(bstates)
        ids_sh = _split_leading(ids, dp)
        if has_aqsgd:
            # the (num_samples, *feat) resid's _split_leading IS the
            # id-shard: localize each lane's ids to its shard rows
            ns = next(fw_bufs[i].resid.shape[0]
                      for i in range(policy.num_boundaries)
                      if policy.at(i).feedback == "aqsgd")
            ids_sh = jax.vmap(
                lambda i, r: shard_ids(i, r, ns, dp))(
                    ids_sh, jnp.arange(dp, dtype=ids.dtype))
        g_dp, new_fw_dp, new_bw_dp, met = jax.vmap(
            compute_grads, in_axes=(None, 0, 0, 0, 0))(
                params, _split_leading(bw_bufs, dp),
                _split_leading(fw_bufs, dp), _split_leading(batch, dp),
                ids_sh)
        grads, new_dp_state = reduce_fn(g_dp, dp_state)
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        new_fw = [_merge_leading(b) for b in new_fw_dp]
        new_bw = [_merge_leading(b) for b in new_bw_dp]
        new_states = _merge_states(new_fw if new_fw else fw_bufs, new_bw)
        metrics = jax.tree.map(jnp.mean, met)
        return params, opt_state, new_states, new_dp_state, metrics

    return step_dp


def _make_tp_lm_train_step(cfg, policy: CompressionPolicy,
                           opt: OptimizerConfig, *, mesh=None,
                           jit: bool = True, dp: int = 1, tp: int = 2,
                           dp_codec: str = "none",
                           dp_feedback: str = "none",
                           dp_k_frac: float = 0.1,
                           data_axis: str = "data",
                           tp_codec: str = "none",
                           tp_feedback: str = "none",
                           tp_k_frac: float = 0.1,
                           tensor_axis: str = "tensor", remat: bool = True):
    """LM training with the dense layer stack sharded over the tensor
    ring (transport/tp_collectives.py), optionally composed with the
    compressed DP gradient all-reduce on a ``(data, 1, tensor)`` mesh.

    Embed + chunked loss run OUTSIDE the shard_map on the global batch
    (exact gradients, like the dp-pipeline path); the stack rides in as a
    separately-differentiated argument (dp-stacked broadcast when
    ``dp > 1``), so its gradient comes back per replica for the
    compressed reduce with no hidden cross-replica psum.  Step signature
    gains a trailing ``tp_state``:
    ``step(params, opt_state, bstates, batch, ids[, dp_state], tp_state)``.
    """
    if cfg.enc_dec:
        raise NotImplementedError("tensor parallelism: decoder-only archs")
    if policy.num_boundaries:
        raise NotImplementedError(
            "simulated boundary cuts + tensor parallelism: run the stage "
            "wire through the pipeline transport (3D mesh) instead")
    from repro.launch.mesh import make_3d_mesh, make_tensor_mesh
    from repro.transport.collectives import make_grad_all_reduce
    from repro.transport.tp_collectives import TPCollectives, tp_apply
    if mesh is None:
        mesh = (make_tensor_mesh(tp, tensor_axis=tensor_axis) if dp == 1
                else make_3d_mesh(dp, 1, tp, data_axis=data_axis,
                                  tensor_axis=tensor_axis))
    tpc = TPCollectives(mesh, tensor_axis, codec=tp_codec, k_frac=tp_k_frac,
                        feedback=tp_feedback)
    stage_fn = transformer.tp_stage_stack_fn(cfg, tpc, remat)
    sites = transformer.tp_sites(cfg)

    def forward(params, stack_in, batch, tp_state):
        labels = jnp.roll(batch["tokens"], -1, axis=1)
        mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
        x = transformer._embed_input(params, batch, cfg)
        # param_dims from the UNSTACKED stack: tp_apply itself accounts
        # for the leading dp replica dim via batch_axis
        y, new_tp = tp_apply(
            stage_fn, stack_in, x, tpc,
            param_dims=transformer.tp_param_dims(params["layers"]),
            state=tp_state,
            batch_axis=(data_axis if dp > 1 else None), sites=sites)
        loss = transformer.hidden_lm_loss(params, y, labels, cfg, mask)
        return loss, new_tp

    def step_tp(params, opt_state, bstates, batch, ids, tp_state):
        (loss, new_tp), (g_params, g_stack) = jax.value_and_grad(
            lambda p, s: forward(p, s, batch, tp_state),
            argnums=(0, 1), has_aux=True)(params, params["layers"])
        grads = dict(g_params)
        grads["layers"] = g_stack
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        metrics = {"loss": loss, "aux": jnp.float32(0.0), "total": loss}
        return params, opt_state, bstates, new_tp, metrics

    def step_dp_tp(params, opt_state, bstates, batch, ids, dp_state,
                   tp_state):
        stack_dp = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (dp, *a.shape)),
            params["layers"])
        (loss, new_tp), (g_params, g_stack_dp) = jax.value_and_grad(
            lambda p, s: forward(p, s, batch, tp_state),
            argnums=(0, 1), has_aux=True)(params, stack_dp)
        reduce_fn = make_grad_all_reduce(
            mesh, data_axis, dp_codec, k_frac=dp_k_frac,
            feedback=dp_feedback, average=False, tp_axis=tensor_axis,
            tp_dims=transformer.tp_param_dims(g_stack_dp))
        g_stack, new_dp_state = reduce_fn(g_stack_dp, dp_state)
        grads = dict(g_params)
        grads["layers"] = g_stack
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        metrics = {"loss": loss, "aux": jnp.float32(0.0), "total": loss}
        return (params, opt_state, bstates, new_dp_state, new_tp, metrics)

    step = step_dp_tp if dp > 1 else step_tp
    return jax.jit(step) if jit else step


def _make_pipeline_lm_train_step(cfg, policy: CompressionPolicy,
                                 opt: OptimizerConfig, *, mesh=None,
                                 stage_axis: str = "stage",
                                 microbatches: Optional[int] = None,
                                 jit: bool = True, donate: bool = True,
                                 schedule: str = "gpipe",
                                 virtual_stages: int = 1, dp: int = 1,
                                 dp_codec: str = "none",
                                 dp_feedback: str = "none",
                                 dp_k_frac: float = 0.1,
                                 data_axis: str = "data", tp: int = 1,
                                 tp_codec: str = "none",
                                 tp_feedback: str = "none",
                                 tp_k_frac: float = 0.1,
                                 tensor_axis: str = "tensor",
                                 remat: bool = True):
    """LM training through the real compressed ``ppermute`` pipeline.

    Same ``step(params, opt_state, bstates, batch, ids)`` signature as the
    simulated path.  With a feedback-free policy ``bstates`` passes through
    (``[]``); with EF/EF21/EF-mixed/AQ-SGD it is the
    :func:`repro.transport.pipeline.init_feedback_state` pytree and the
    step returns the updated buffers (bw side read from the gradient).
    With the interleaved schedule the layer stack splits into
    ``num_stages * virtual_stages`` logical slices (round-robin per
    device).  MoE aux losses are not threaded through the pipeline
    (stage_fn is single-tensor); fine for the dense smoke archs this path
    targets.
    """
    if cfg.enc_dec:
        raise NotImplementedError("pipeline transport: decoder-only archs")
    from repro.transport.pipeline import pipeline_apply
    bp = _uniform_boundary(policy)
    s_stages = policy.num_stages
    needs_state = bp.needs_fw_buffer or bp.needs_bw_buffer
    if tp > 1 and tp_feedback != "none":
        raise NotImplementedError(
            "pipeline + tensor parallelism: feedback-free tensor wires only "
            "(EF/EF21 state does not thread through pipeline_apply yet)")
    if dp > 1:
        from repro.launch.mesh import make_3d_mesh, make_dp_pipeline_mesh
        if mesh is None:
            mesh = (make_dp_pipeline_mesh(dp, s_stages, data_axis=data_axis,
                                          stage_axis=stage_axis) if tp == 1
                    else make_3d_mesh(dp, s_stages, tp, data_axis=data_axis,
                                      stage_axis=stage_axis,
                                      tensor_axis=tensor_axis))
        return _make_dp_pipeline_lm_train_step(
            cfg, bp, opt, mesh=mesh, stage_axis=stage_axis,
            data_axis=data_axis, microbatches=microbatches, jit=jit,
            schedule=schedule, virtual_stages=virtual_stages, dp=dp,
            dp_codec=dp_codec, dp_feedback=dp_feedback,
            dp_k_frac=dp_k_frac, s_stages=s_stages, tp=tp,
            tp_codec=tp_codec, tp_k_frac=tp_k_frac, tensor_axis=tensor_axis,
            remat=remat)
    if tp > 1:
        from repro.launch.mesh import make_3d_mesh
        if mesh is None:
            mesh = make_3d_mesh(1, s_stages, tp, data_axis=data_axis,
                                stage_axis=stage_axis,
                                tensor_axis=tensor_axis)
    else:
        mesh = _pipeline_mesh(policy, mesh, stage_axis)
    stage_fn, tp_kwargs = _tp_stage_fn(cfg, mesh, tp, tp_codec, tp_k_frac,
                                       tensor_axis, remat)

    def forward(params, batch, fw_state, bw_state, ids):
        labels = jnp.roll(batch["tokens"], -1, axis=1)
        mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
        x = transformer._embed_input(params, batch, cfg)
        stack = transformer.stack_layer_stages(params,
                                               s_stages * virtual_stages)
        new_fw = None
        if needs_state:
            x, new_fw = pipeline_apply(
                stage_fn, stack, x, mesh, stage_axis,
                policy=bp, microbatches=microbatches, schedule=schedule,
                virtual_stages=virtual_stages,
                fw_state=fw_state, bw_state=bw_state, ids=ids,
                **tp_kwargs(stack))
        else:
            x = pipeline_apply(stage_fn, stack, x, mesh, stage_axis,
                               policy=bp,
                               microbatches=microbatches, schedule=schedule,
                               virtual_stages=virtual_stages,
                               **tp_kwargs(stack))
        loss = transformer.hidden_lm_loss(params, x, labels, cfg, mask)
        return loss, new_fw

    def step(params, opt_state, bstates, batch, ids):
        loss, grads = jax.value_and_grad(
            lambda p: forward(p, batch, None, None, ids)[0])(params)
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        metrics = {"loss": loss, "aux": jnp.float32(0.0), "total": loss}
        return params, opt_state, bstates, metrics

    def step_feedback(params, opt_state, bstates, batch, ids):
        def loss_fn(params, bw_state):
            return forward(params, batch, bstates["fw"], bw_state, ids)
        (loss, new_fw), (grads, new_bw) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(params, bstates["bw"])
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        metrics = {"loss": loss, "aux": jnp.float32(0.0), "total": loss}
        return params, opt_state, {"fw": new_fw, "bw": new_bw}, metrics

    step = step_feedback if needs_state else step
    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def mesh_wire_bytes(cfg, parallel: ParallelSpec, batch: int, seq: int, *,
                    microbatches: Optional[int] = None,
                    remat: bool = True) -> dict:
    """What one device puts on each ring of the pipeline mesh in one step
    of :func:`make_lm_train_step` with ``parallel=`` (GPipe, its default
    schedule): per ring (``"stage"``, ``"tensor"``) the hops it sends,
    the exact payload bytes of one hop (the codec registry's packed
    shapes: ``PipelineTransport``'s payload structs and
    ``tp_wire_report``), their product ``bytes``, the elements one hop
    encodes and the route that packs them (``transport.codecs.
    wire_route``).  Pure host arithmetic.

    Stage ring: every tick of the schedule scan sends one forward and one
    backward hop (the fill and drain ticks and the wrap-around hop
    included).  Tensor ring: each block gathers and scatters twice
    forward (attention, MLP) and twice backward; with ``remat`` the
    recomputed forward sends all of its collectives again except the
    group's last scatter, whose result the backward does not read.  Each
    collective is ``tp - 1`` hops of one sequence shard."""
    from repro.core.policy import BoundaryPolicy
    from repro.models.common import DTYPE
    from repro.transport.codecs import codec_for, wire_route
    from repro.transport.pipeline import PipelineTransport, wire_telemetry
    from repro.transport.schedules import as_schedule
    from repro.transport.tp_collectives import tp_wire_report
    s_stages, tp = parallel.stages, parallel.tp
    sched = as_schedule("gpipe")
    mb = microbatches or s_stages
    mbsz = batch // (mb * parallel.dp)
    shard = (mbsz, seq // tp, cfg.d_model)
    n = shard[1] * shard[2]
    ticks = sched.num_ticks(mb, s_stages)
    out = {}
    if s_stages > 1:
        pol = parallel.stage_policy()
        bp = BoundaryPolicy() if pol is None else _uniform_boundary(pol)
        tel = wire_telemetry(PipelineTransport(bp, "stage", s_stages), sched,
                             shard, DTYPE, microbatches=mb)
        fw, bw = (tel["fw_payload_bytes_per_hop"],
                  tel["bw_payload_bytes_per_hop"])
        out["stage"] = {"hops": 2 * ticks,
                        "payload_bytes_per_hop": (fw + bw) // 2,
                        "bytes": ticks * (fw + bw), "elems_per_hop": mbsz * n,
                        "route": wire_route(codec_for(bp.fw).name,
                                            (mbsz, n))}
    if tp > 1:
        codec = parallel.tensor.codec
        per_hop = tp_wire_report((mbsz, seq, cfg.d_model), tp, codec,
                                 k_frac=parallel.tensor.k_frac,
                                 dtype=DTYPE)["payload_bytes_per_hop"]
        blocks = len(cfg.layer_kinds())
        per_group = 8 * blocks + ((4 * blocks - 1) if remat else 0)
        hops = (ticks * (cfg.num_groups // s_stages) * per_group
                * (tp - 1))
        out["tensor"] = {"hops": hops, "payload_bytes_per_hop": per_hop,
                         "bytes": hops * per_hop, "elems_per_hop": mbsz * n,
                         # pack_grad_leaf flattens each shard to one row
                         "route": wire_route(codec, (1, mbsz * n))}
    return out


def _make_dp_pipeline_lm_train_step(cfg, bp, opt: OptimizerConfig, *, mesh,
                                    stage_axis: str, data_axis: str,
                                    microbatches: Optional[int],
                                    jit: bool, schedule: str,
                                    virtual_stages: int, dp: int,
                                    dp_codec: str, dp_feedback: str,
                                    dp_k_frac: float, s_stages: int,
                                    tp: int = 1, tp_codec: str = "none",
                                    tp_k_frac: float = 0.1,
                                    tensor_axis: str = "tensor",
                                    remat: bool = True):
    """LM training on the 2D ``(data, stages)`` mesh: every replica row
    pipelines its contiguous batch shard through the compressed
    ``ppermute`` wire, and the per-replica LAYER-STACK gradients cross the
    ``data`` axis through the compressed all-reduce
    (transport/collectives.py).  The stack rides into the loss as a
    dp-stacked broadcast copy, so its gradient comes back per replica with
    no hidden ``psum``; embed/head/norm run replicated on the global batch
    and keep exact gradients.  Step signature:
    ``step(params, opt_state, bstates, batch, ids, dp_state)``.

    Boundary feedback composes with dp: ``bstates`` is the
    :func:`repro.transport.pipeline.init_feedback_state` pytree built with
    ``dp=dp`` (leading replica dim, sharded over the ``data`` axis — each
    replica row compensates its own batch shard; AQ-SGD id-shards), and
    the bw side comes back as the gradient w.r.t. ``bstates["bw"]``,
    exactly like the solo pipeline step.
    """
    from repro.transport.pipeline import pipeline_apply
    from repro.transport.collectives import make_grad_all_reduce
    # shard the reduce over the stage axis too: each stage column rings
    # only its own slice of the stack gradient (which pipeline_apply
    # already leaves P(stage)-sharded — no reshard gather).  With tp > 1
    # the reduce is additionally tensor-sharded per leaf, so it is built
    # at trace time in _finish (the tp_dims tree needs the grad pytree).
    reduce_fn = None
    if tp == 1:
        reduce_fn = make_grad_all_reduce(
            mesh, data_axis, dp_codec, k_frac=dp_k_frac,
            feedback=dp_feedback, average=False, shard_axis=stage_axis)
    stage_fn, tp_kwargs = _tp_stage_fn(cfg, mesh, tp, tp_codec, tp_k_frac,
                                       tensor_axis, remat)
    n_slices = s_stages * virtual_stages
    needs_state = bp.needs_fw_buffer or bp.needs_bw_buffer

    def forward_dp(params, stack_dp, batch, ids, fw_state, bw_state):
        labels = jnp.roll(batch["tokens"], -1, axis=1)
        mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
        x = transformer._embed_input(params, batch, cfg)
        new_fw = None
        if needs_state:
            x, new_fw = pipeline_apply(
                stage_fn, stack_dp, x, mesh,
                stage_axis, policy=bp, microbatches=microbatches,
                schedule=schedule, virtual_stages=virtual_stages,
                dp_axis=data_axis, fw_state=fw_state, bw_state=bw_state,
                ids=ids, **tp_kwargs(stack_dp))
        else:
            x = pipeline_apply(
                stage_fn, stack_dp, x, mesh,
                stage_axis, policy=bp, microbatches=microbatches,
                schedule=schedule, virtual_stages=virtual_stages,
                dp_axis=data_axis, **tp_kwargs(stack_dp))
        loss = transformer.hidden_lm_loss(params, x, labels, cfg, mask)
        return loss, new_fw

    def _stack_dp(params):
        stack = transformer.stack_layer_stages(params, n_slices)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (dp, *a.shape)), stack)

    def _finish(params, opt_state, g_params, g_stack_dp, dp_state, loss):
        rf = reduce_fn
        if rf is None:
            rf = make_grad_all_reduce(
                mesh, data_axis, dp_codec, k_frac=dp_k_frac,
                feedback=dp_feedback, average=False, shard_axis=stage_axis,
                tp_axis=tensor_axis,
                tp_dims=transformer.tp_param_dims(g_stack_dp))
        g_stack, new_dp_state = rf(g_stack_dp, dp_state)
        grads = dict(g_params)
        grads["layers"] = jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]),
            g_stack)
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        metrics = {"loss": loss, "aux": jnp.float32(0.0), "total": loss}
        return params, opt_state, new_dp_state, metrics

    def step(params, opt_state, bstates, batch, ids, dp_state):
        loss, (g_params, g_stack_dp) = jax.value_and_grad(
            lambda p, s: forward_dp(p, s, batch, ids, None, None)[0],
            argnums=(0, 1))(params, _stack_dp(params))
        params, opt_state, new_dp_state, metrics = _finish(
            params, opt_state, g_params, g_stack_dp, dp_state, loss)
        return params, opt_state, bstates, new_dp_state, metrics

    def step_feedback(params, opt_state, bstates, batch, ids, dp_state):
        def loss_fn(params, stack_dp, bw_state):
            return forward_dp(params, stack_dp, batch, ids,
                              bstates["fw"], bw_state)
        (loss, new_fw), (g_params, g_stack_dp, new_bw) = jax.value_and_grad(
            loss_fn, argnums=(0, 1, 2), has_aux=True)(
                params, _stack_dp(params), bstates["bw"])
        params, opt_state, new_dp_state, metrics = _finish(
            params, opt_state, g_params, g_stack_dp, dp_state, loss)
        return (params, opt_state, {"fw": new_fw, "bw": new_bw},
                new_dp_state, metrics)

    step = step_feedback if needs_state else step
    return jax.jit(step) if jit else step


def make_lm_eval_step(cfg, policy: CompressionPolicy, compress: bool):
    mod = encdec if cfg.enc_dec else transformer

    @jax.jit
    def step(params, batch):
        logits = mod.forward_eval(params, batch, cfg, policy,
                                  compress=compress)
        labels = jnp.roll(batch["tokens"], -1, axis=1)
        mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
        return lm_loss(logits, labels, mask)

    return step


# ---------------------------------------------------------------------------
# Image-classification train step (paper's ResNet18/CIFAR-10 experiments)
# ---------------------------------------------------------------------------

def xent_loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def make_cnn_train_step(policy: CompressionPolicy, opt: OptimizerConfig,
                        transport: str = "simulated", mesh=None,
                        stage_axis: str = "stage",
                        pipeline_microbatches: Optional[int] = None,
                        schedule: str = "gpipe", virtual_stages: int = 1,
                        boundary_feat=None):
    from repro.models import cnn

    policy = _resolve_rules(policy, boundary_feat)
    if transport == "pipeline":
        return _make_pipeline_cnn_train_step(
            policy, opt, mesh=mesh, stage_axis=stage_axis,
            microbatches=pipeline_microbatches, schedule=schedule,
            virtual_stages=virtual_stages)
    if transport != "simulated":
        raise ValueError(f"unknown transport {transport!r}")

    def loss_fn(params, bw_bufs, fw_bufs, images, labels, ids):
        bstates = _merge_states(fw_bufs, bw_bufs)
        logits, new_fw = cnn.forward_train(params, images, policy,
                                           bstates or None, ids)
        return xent_loss(logits, labels), (logits, new_fw)

    @jax.jit
    def step(params, opt_state, bstates, images, labels, ids):
        fw_bufs, bw_bufs = _split_states(bstates)
        (loss, (logits, new_fw)), (grads, new_bw) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(
                params, bw_bufs, fw_bufs, images, labels, ids)
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        acc = (logits.argmax(-1) == labels).mean()
        new_states = _merge_states(new_fw if new_fw else fw_bufs, new_bw)
        return params, opt_state, new_states, {"loss": loss, "acc": acc}

    return step


def _make_pipeline_cnn_train_step(policy: CompressionPolicy,
                                  opt: OptimizerConfig, *, mesh=None,
                                  stage_axis: str = "stage",
                                  microbatches: Optional[int] = None,
                                  schedule: str = "gpipe",
                                  virtual_stages: int = 1):
    """CNN training through the real compressed ``ppermute`` pipeline.

    Uses the homogeneous-stage CNN (models/cnn.py ``init_pipeline_params``
    — with the interleaved schedule, built with ``S * virtual_stages``
    logical stages); stem + head run replicated, the residual stages
    pipeline over the mesh with packed fw/bw payloads under ``schedule``.
    Signature matches the simulated step; with a feedback policy
    ``bstates`` is the ``init_feedback_state`` pytree and comes back
    updated (bw side via the gradient), otherwise it passes through
    unchanged.
    """
    from repro.models import cnn
    from repro.transport.pipeline import pipeline_apply
    bp = _uniform_boundary(policy)
    mesh = _pipeline_mesh(policy, mesh, stage_axis)
    needs_state = bp.needs_fw_buffer or bp.needs_bw_buffer

    def forward(params, images, labels, fw_state, bw_state, ids):
        x = cnn.pipeline_stem(params, images)
        new_fw = None
        if needs_state:
            x, new_fw = pipeline_apply(
                cnn.pipeline_stage_apply, params["stages"], x, mesh,
                stage_axis, policy=bp, microbatches=microbatches,
                schedule=schedule, virtual_stages=virtual_stages,
                fw_state=fw_state, bw_state=bw_state, ids=ids)
        else:
            x = pipeline_apply(cnn.pipeline_stage_apply, params["stages"],
                               x, mesh, stage_axis, policy=bp,
                               microbatches=microbatches, schedule=schedule,
                               virtual_stages=virtual_stages)
        logits = cnn.pipeline_head(params, x)
        return xent_loss(logits, labels), (logits, new_fw)

    @jax.jit
    def step(params, opt_state, bstates, images, labels, ids):
        (loss, (logits, _)), grads = jax.value_and_grad(
            forward, has_aux=True)(params, images, labels, None, None, ids)
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        acc = (logits.argmax(-1) == labels).mean()
        return params, opt_state, bstates, {"loss": loss, "acc": acc}

    @jax.jit
    def step_feedback(params, opt_state, bstates, images, labels, ids):
        def loss_fn(params, bw_state):
            return forward(params, images, labels, bstates["fw"],
                           bw_state, ids)
        (loss, (logits, new_fw)), (grads, new_bw) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(params, bstates["bw"])
        params, opt_state = apply_updates(opt, params, grads, opt_state)
        acc = (logits.argmax(-1) == labels).mean()
        return (params, opt_state, {"fw": new_fw, "bw": new_bw},
                {"loss": loss, "acc": acc})

    return step_feedback if needs_state else step


def make_cnn_eval_step(policy: CompressionPolicy, compress: bool,
                       transport: str = "simulated"):
    from repro.models import cnn

    fwd = (cnn.pipeline_forward_eval if transport == "pipeline"
           else cnn.forward_eval)

    @jax.jit
    def step(params, images, labels):
        logits = fwd(params, images, policy, compress=compress)
        return (logits.argmax(-1) == labels).mean(), xent_loss(logits, labels)

    return step
