"""Compression boundary: ``jax.custom_vjp`` around a SimulatedTransport.

A boundary sits at a pipeline-stage cut.  In a real MP system the forward
activation and the backward activation-gradient cross the network here; the
paper compresses both.  Following the paper (Sec. 2.1) we integrate the
boundary directly into the model with ``jax.custom_vjp`` — convergence-
equivalent to the distributed system.  The compression itself is delegated
to :class:`repro.transport.simulated.SimulatedTransport`, which implements
the shared ``Transport.fw/bw`` interface over the wire-codec registry
(repro/transport/codecs.py) — the same registry the real differentiable
``ppermute`` pipeline (repro/transport/pipeline.py) packs bytes with, so
both paths see identical numbers at the boundary.

Semantics (training):
  forward : y  = F(x)   where F is the fw compressor, optionally wrapped in
                         EF / EF21 / EF-mixed / AQ-SGD feedback;
  backward: gx = G(gy)  where G is the bw compressor, optionally wrapped in
                         EF / EF21 / EF-mixed feedback, or — with
                         ``reuse_indices`` — masking by the forward TopK mask.

State threading: feedback buffers are functional.  The *forward* buffer's
update is returned as a second output.  The *backward* buffer's update is
only known during backprop, so it is returned **as the cotangent of the
``bw_buf`` argument** — take ``grad`` w.r.t. ``bw_buf`` in the train step and
read the updated buffer out of the gradient pytree (see train/steps.py).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.policy import BoundaryPolicy
from repro.obs.trace import scope


def _transport(policy: BoundaryPolicy):
    # Lazy: repro.core.__init__ imports this module, and the transport
    # package imports repro.core.policy — a top-level import would cycle.
    from repro.transport.simulated import simulated_transport
    return simulated_transport(policy)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def boundary_apply(policy: BoundaryPolicy, x, fw_buf, bw_buf, ids):
    """Training-time boundary.  Returns ``(y, new_fw_state)``.

    ``fw_buf``/``bw_buf``: per-direction
    :class:`~repro.core.feedback.FeedbackState` (``resid`` size-0 when the
    direction has no feedback).  ``ids``: (B,) int32 example ids (AQ-SGD
    only; zeros otherwise).  The updated backward state is delivered as
    the cotangent of ``bw_buf``.
    """
    m, new_fw, _ = _transport(policy).fw(x, fw_buf, ids)
    return m, new_fw


def _boundary_fwd(policy: BoundaryPolicy, x, fw_buf, bw_buf, ids):
    m, new_fw, ctx = _transport(policy).fw(x, fw_buf, ids)
    residuals = (ctx, fw_buf, bw_buf, ids)
    return (m, new_fw), residuals


def _boundary_bwd(policy: BoundaryPolicy, residuals, cotangents):
    ctx, fw_buf, bw_buf, ids = residuals
    g_y, _g_new_fw = cotangents          # buffer output is aux — no gradient
    g_x, new_bw = _transport(policy).bw(g_y, bw_buf, ctx)
    zero_fw = jax.tree.map(jnp.zeros_like, fw_buf)
    zero_ids = np.zeros(ids.shape, dtype=jax.dtypes.float0)
    return (g_x, zero_fw, new_bw, zero_ids)


boundary_apply.defvjp(_boundary_fwd, _boundary_bwd)


@scope("codec")
def boundary_eval(policy: BoundaryPolicy, x, compress: bool):
    """Inference-time boundary: plain fw compressor or identity.

    The paper evaluates each trained model BOTH ways (Tables 1-4):
    compression kept on at inference vs switched off.
    """
    return policy.fw(x) if compress else x


@scope("codec")
def boundary_wire_eval(policy: BoundaryPolicy, x, compress: bool):
    """Serve-time boundary through the wire-codec registry.

    Unlike :func:`boundary_eval` (the in-process C(x)), this actually packs
    the stage-cut tensor into the same q8/TopK payload pytree the training
    pipeline puts on the wire (transport/codecs.py) and unpacks it on the
    "receiving" stage — a served decode exercises the real byte format.

    Packing is PER REQUEST (vmap over the batch dim): each serving slot is
    an independent stream on a real wire, so quantization scales are
    computed per request.  This also keeps a slot's numerics independent of
    its batch neighbours — the property that makes continuous-batching
    output bit-identical to solo generation.  TopK is per-example in the
    codec already; q8/q4 get per-request (rather than per-microbatch)
    scales, the only difference from the training-time payload.
    """
    if not compress or policy.fw.kind == "none":
        return x
    from repro.transport.codecs import codec_for
    codec = codec_for(policy.fw)
    k_frac = policy.fw.k_frac

    def one(xe):
        payload = codec.pack(xe[None], k_frac)
        return codec.unpack(payload, (1,) + xe.shape, xe.dtype)[0]

    return jax.vmap(one)(x)


@scope("codec")
def boundary_wire_eval_tokens(policy: BoundaryPolicy, x, compress: bool):
    """Per-(request, token) wire packing for multi-token decode spans.

    ``x``: (B, T, d).  Each token's cut tensor is packed as its OWN payload
    — exactly the granularity :func:`boundary_wire_eval` gives a T=1
    decode tick (the codec sees a (1, d) tensor either way, so scales and
    TopK counts are identical).  This is what keeps a speculative
    verification span's numerics bit-identical to plain per-token greedy
    decode, and it is the byte stream a draft/target pair sharing this
    stage cut would actually exchange.
    """
    if not compress or policy.fw.kind == "none":
        return x
    from repro.transport.codecs import codec_for
    codec = codec_for(policy.fw)
    k_frac = policy.fw.k_frac

    def one(xt):                                          # (d,)
        payload = codec.pack(xt[None], k_frac)
        return codec.unpack(payload, (1,) + xt.shape, xt.dtype)[0]

    return jax.vmap(jax.vmap(one))(x)


def boundary_wire_bytes_per_token(policy, d_model: int,
                                  num_cuts: Optional[int] = None) -> float:
    """Bytes per decoded token crossing the stage cuts of a
    :class:`~repro.core.policy.CompressionPolicy` (serve metrics).

    ``num_cuts``: the EFFECTIVE cut count — ``segment_bounds`` caps the
    stage count at the model's group count, so a 4-stage policy on a
    2-group smoke model has 1 cut, not ``policy.num_boundaries``.
    Defaults to ``policy.num_boundaries`` when the caller's stack really
    has that many cuts.
    """
    from repro.transport.codecs import codec_for
    total = 0.0
    cuts = policy.num_boundaries if num_cuts is None else num_cuts
    for i in range(cuts):
        bp = policy.at(i)
        codec = codec_for(bp.fw)
        total += codec.wire_bytes_per_elem(d_model, 2, bp.fw.k_frac) * d_model
    return total


# ---------------------------------------------------------------------------
# State container helpers
# ---------------------------------------------------------------------------

def empty_boundary_state(dtype=jnp.float32):
    """Buffer-free ``{'fw', 'bw'}`` FeedbackState pair — what a boundary
    without feedback threads through :func:`boundary_apply` (size-0
    ``resid``, stable pytree structure across policies)."""
    from repro.core.feedback import init_feedback
    return {"fw": init_feedback("none", (), direction="fw", dtype=dtype),
            "bw": init_feedback("none", (), direction="bw", dtype=dtype)}


def init_boundary_state(policy: BoundaryPolicy, feat_shape, *, batch: int,
                        num_samples: int = 0, dtype=jnp.float32):
    """``{'fw': FeedbackState, 'bw': FeedbackState}`` for one boundary
    (``resid`` is size-0 when the direction has no feedback)."""
    from repro.core.feedback import init_feedback
    fw = init_feedback(policy.feedback, feat_shape, direction="fw",
                       dtype=dtype, num_samples=num_samples, batch=batch)
    bw = init_feedback(policy.bw_feedback, feat_shape, direction="bw",
                       dtype=dtype, num_samples=num_samples, batch=batch)
    return {"fw": fw, "bw": bw}


def init_all_boundary_states(comp_policy, feat_shape, *, batch: int,
                             num_samples: int = 0, dtype=jnp.float32):
    """One state dict per boundary of a CompressionPolicy."""
    return [init_boundary_state(comp_policy.at(i), feat_shape, batch=batch,
                                num_samples=num_samples, dtype=dtype)
            for i in range(comp_policy.num_boundaries)]
