"""Mixture-of-Experts FFN: GShard-style grouped capacity-based dispatch.

TPU-native formulation: tokens are split into groups of ``group_size``;
within a group, routing is materialized as dispatch/combine one-hot tensors
``(g, E, C)`` applied with einsums.  Under expert-parallel sharding the
group axis is data-sharded and the expert axis is expert-sharded, so the
``(G,E,C,d)`` expert-input tensor changes sharding between the dispatch
einsum and the expert matmuls — XLA lowers exactly that re-sharding to an
all-to-all.  Capacity C = ceil(cf * g * top_k / E) bounds expert work and
keeps the dispatch tensor O(T * g * k * cf) instead of O(T^2).

Supports mixtral (8e top-2) and llama4-maverick (128e top-1 + shared
expert, interleaved every 2nd layer).  Router in fp32 with Switch-style
load-balance auxiliary loss.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import DTYPE, dense_init, mlp_apply, mlp_init
from repro.obs.trace import scope
from repro.sharding.ctx import constrain

GROUP_SIZE = 4096        # tokens per routing group (MaxText-like)


def moe_init(key, d: int, ff: int, num_experts: int, mlp_kind: str,
             num_shared: int = 0, dtype=DTYPE):
    ks = jax.random.split(key, num_experts + 2)
    expert = jax.vmap(lambda k: mlp_init(k, d, ff, mlp_kind, dtype))(
        jnp.stack(ks[:num_experts]))
    params = {"router": dense_init(ks[-1], d, num_experts, jnp.float32),
              "experts": expert}
    if num_shared:
        params["shared"] = mlp_init(ks[-2], d, ff * num_shared, mlp_kind, dtype)
    return params


def _route(logits: jnp.ndarray, top_k: int, cap: int, num_experts: int):
    """logits: (G, g, E) fp32 -> dispatch (G,g,E,C) token dtype-agnostic,
    combine (G,g,E,C) fp32, aux loss scalar."""
    gg, g, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)            # (G,g,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch load-balance loss on top-1 assignment
    me = probs.mean(axis=1)                                      # (G,E)
    top1 = jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32)
    ce = top1.mean(axis=1)                                       # (G,E)
    aux = e * jnp.mean(jnp.sum(me * ce, axis=-1))

    # slot position of each (token, choice) within its expert, per group.
    # choices flattened in priority order: all top-1 first, then top-2 ...
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)        # (G,g,k,E)
    flat = onehot.transpose(0, 2, 1, 3).reshape(gg, g * top_k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                        # (G,g*k,E)
    pos = (pos * flat).sum(-1).reshape(gg, top_k, g).transpose(0, 2, 1)
    keep = pos < cap                                             # (G,g,k)
    gate_vals = gate_vals * keep

    slot_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=jnp.float32)[..., :cap]       # (G,g,k,C)
    exp_oh = onehot.astype(jnp.float32)                          # (G,g,k,E)
    dispatch = jnp.einsum("Ggke,Ggkc->Ggec", exp_oh,
                          slot_oh * keep[..., None].astype(jnp.float32))
    combine = jnp.einsum("Ggke,Ggkc->Ggec", exp_oh,
                         slot_oh * gate_vals[..., None])
    return dispatch, combine, aux


def _quant_dispatch(t: jnp.ndarray, spec) -> jnp.ndarray:
    """BEYOND-PAPER: int8-quantize the expert-dispatch payload across the
    EP all-to-all (the paper compresses only pipeline-stage handoffs; the
    same insight applies to the (E,G,C,d) dispatch tensor, which §Roofline
    shows dominates MoE collective bytes).  Per-(expert,group,slot) scales
    ride along as fp32 — 1/513 of the payload.  Straight-through estimator
    in backward (the quantization is on the wire, not in the math).
    """
    from repro.core.compressors import quantize_kbit, dequantize_kbit
    from repro.sharding.ctx import constrain as _c

    @jax.custom_vjp
    def qdq(t):
        codes, mn, sc = quantize_kbit(t.astype(jnp.float32), 8, axis=(3,))
        codes = _c(codes.astype(jnp.int8), *spec)       # int8 on the wire
        mn = _c(mn, *spec)
        sc = _c(sc, *spec)
        return dequantize_kbit(codes.astype(jnp.uint8), mn, sc,
                               jnp.float32).astype(t.dtype)

    def fwd(t):
        return qdq(t), None

    def bwd(_, g):
        # paper-symmetric: the backward all-to-all payload (the gradient
        # w.r.t. the dispatched tokens) is quantized the same way
        codes, mn, sc = quantize_kbit(g.astype(jnp.float32), 8, axis=(3,))
        codes = _c(codes.astype(jnp.int8), *spec)
        gq = dequantize_kbit(codes.astype(jnp.uint8), _c(mn, *spec),
                             _c(sc, *spec), jnp.float32)
        return (gq.astype(g.dtype),)

    qdq.defvjp(fwd, bwd)
    return qdq(t)


def _moe_apply_dense(params, x: jnp.ndarray, *, num_experts: int,
                     top_k: int, mlp_kind: str, dispatch_quant: bool = False
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless dense routing (inference paths).

    The grouped capacity heuristic is LENGTH-DEPENDENT: C = ceil(cf * g *
    k / E) and the group composition both change with the total token
    count, so the same token can be dropped in one forward and routed in
    another — decode (t = B tokens per group, C collapses to 1) drifted
    from prefill, and a 30-token prefill drops different tokens than a
    32-token one.  Inference therefore routes densely: every expert runs
    on every token, combined with the (renormalized) top-k gates —
    identical expert math to the capacity path for kept tokens, and
    nothing is ever dropped.  (A production server would realize the same
    dropless semantics with grouped GEMMs instead of the dense E-way
    fan-out; capacity routing stays on the training path, where bounded
    expert work is the point.)
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = xt.astype(jnp.float32) @ params["router"]           # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)            # (T,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    combine = jnp.sum(
        jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.float32)
        * gate_vals[..., None], axis=1)                          # (T,E)
    top1 = jax.nn.one_hot(gate_idx[..., 0], num_experts, dtype=jnp.float32)
    aux = num_experts * jnp.sum(probs.mean(0) * top1.mean(0))
    ex_in = xt
    if dispatch_quant:
        # same wire semantics as _quant_dispatch: the token vectors the
        # experts receive are int8-quantized along d (straight-through)
        from repro.core.compressors import quantize_dequantize
        qdq = quantize_dequantize(ex_in.astype(jnp.float32), 8,
                                  axis=(1,)).astype(ex_in.dtype)
        ex_in = ex_in + jax.lax.stop_gradient(qdq - ex_in)
    ex_out = jax.vmap(lambda p: mlp_apply(p, ex_in, mlp_kind))(
        params["experts"])                                       # (E,T,d)
    y = jnp.einsum("etd,te->td", ex_out, combine.astype(ex_out.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], xt, mlp_kind)
    return y.reshape(b, s, d), aux


@scope("mlp")
def moe_apply(params, x: jnp.ndarray, *, num_experts: int, top_k: int,
              mlp_kind: str, capacity_factor: float = 1.25,
              group_size: int = GROUP_SIZE, dispatch_quant: bool = False,
              dropless: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d).  Returns (y, aux_loss).  ``dropless`` (inference)
    switches to dense routing — see :func:`_moe_apply_dense`; single-token
    decode always routes densely (capacity degenerates to C=1 there)."""
    b, s, d = x.shape
    if dropless or s == 1:
        return _moe_apply_dense(params, x, num_experts=num_experts,
                                top_k=top_k, mlp_kind=mlp_kind,
                                dispatch_quant=dispatch_quant)
    t = b * s
    g = min(group_size, t)
    while t % g:
        g //= 2
    gg = t // g
    cap = max(top_k, int(math.ceil(capacity_factor * g * top_k / num_experts)))

    xt = x.reshape(gg, g, d)
    xt = constrain(xt, "batch", None, None)
    logits = xt.astype(jnp.float32) @ params["router"]           # (G,g,E)
    dispatch, combine, aux = _route(logits, top_k, cap, num_experts)
    # §Perf (EXPERIMENTS.md, mixtral hillclimb 1): the routing one-hots are
    # the LARGEST tensors in the layer (G*g*E*C).  Pin them group-sharded so
    # the partitioner never all-gathers them over the data axis — the
    # inter-device traffic must be the small (E,G,C,d) expert-input tensor.
    dispatch = constrain(dispatch, "batch", None, None, None)
    combine = constrain(combine, "batch", None, None, None)

    # dispatch einsum contracts g (group-local): compute with G sharded,
    # THEN reshard to expert-parallel — exactly one all-to-all on ex_in.
    # (§Perf iteration 3 tried d replicated here — all-gather bytes grew
    # 4.5x because the dispatch einsum's transpose then re-gathered the
    # full (G,E,C,d) tensor over data; d-over-model is the right layout.)
    ex_in = jnp.einsum("Ggd,Ggec->eGcd", xt, dispatch.astype(xt.dtype))
    if dispatch_quant:
        ex_in = _quant_dispatch(ex_in, ("expert", None, None, "model"))
    else:
        ex_in = constrain(ex_in, "expert", None, None, "model")
    # (§Perf iteration 4 tried an explicit bf16 d-gather here — the
    # constraint's transpose re-gathered the tensor over data in backward,
    # +4.6x all-gather.  The partitioner's implicit gather wins; its
    # f32-before-gather ordering is a CPU-backend artifact only.)
    ex_out = jax.vmap(lambda p, h: mlp_apply(p, h.reshape(-1, d), mlp_kind
                                             ).reshape(gg, cap, d),
                      in_axes=(0, 0))(params["experts"], ex_in)  # (E,G,C,d)
    # reshard BACK to group-sharded before the combine einsum so the
    # combine contraction (over e, c) is local in G — the reverse
    # all-to-all happens on ex_out, not by gathering `combine`.
    ex_out = constrain(ex_out, None, "batch", None, "model")
    # §Perf iteration 2: keep the big (E,G,C,d) tensor bf16 on the wire —
    # fp32 accumulation happens in the MXU (preferred_element_type), not by
    # materializing an fp32 copy that doubles the all-gather bytes.
    y = jnp.einsum("eGcd,Ggec->Ggd", ex_out,
                   combine.astype(ex_out.dtype),
                   preferred_element_type=jnp.float32)
    y = constrain(y.astype(x.dtype), "batch", None, None)

    if "shared" in params:
        y = y + mlp_apply(params["shared"], xt, mlp_kind)
    return y.reshape(b, s, d), aux
