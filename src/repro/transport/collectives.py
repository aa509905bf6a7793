"""Compressed data-parallel gradient all-reduce over the wire codecs.

The gradient-side twin of the pipeline's compressed activation hops: on a
``(data, stages)`` mesh every replica owns the gradient of its batch shard,
and what crosses the ``data`` axis is a PACKED payload from the same
wire-codec registry the stage boundaries use (transport/codecs.py) — the
paper's activation-compression and gradient-compression regimes finally run
simultaneously on one mesh (paper Tables 2-3: gradients tolerate milder
rates than activations; error feedback rescues aggressive ones).

Scheme (the standard compress-then-exchange all-reduce, cf. Agarwal et al.,
*On the Utility of Gradient Compression in Distributed Training Systems*):

  1. every replica packs each parameter-leaf gradient with one codec call
     (per-leaf per-tensor scales; ragged/odd-sized leaves hit the q4 pad
     path), optionally error-compensated by PER-REPLICA residual buffers;
  2. all per-leaf payloads are FUSED into one contiguous uint8 buffer (the
     1F1B fused-hop trick — one collective launch per ring hop instead of
     one per payload leaf);
  3. the buffers ride a ``ppermute`` ring over the data axis (``dp - 1``
     hops), each replica keeping the received buffers in a list by hop
     (:func:`ring_exchange`; the hop is static, so no buffer is written
     or read at a traced position);
  4. every replica decodes each hop's payload and sums the ``dp`` decoded
     values in source-rank order, choosing each source's hop with a select
     fused into the decode (:func:`by_source`) — a fixed association, so
     all replicas compute a bitwise identical reduced gradient (ring-order
     sums would diverge per rank).

``codec="none"`` is a RAW passthrough (native dtype, no bf16 downcast), so
an uncompressed DP reduce is bit-exact against serial gradient summation —
the acceptance baseline.  Error feedback (the gradient-axis analog of the
PR-2 boundary buffers; buffers ride the train state, see
:func:`init_dp_state`):

  * ``ef``   — send C(g + e);                 e' = g + e - C(g + e)
  * ``ef21`` — send the delta C(g - w);       w' = w + C(g - w), and the
               receivers reconstruct the sum from a REPLICATED aggregate
               G = sum_r w_r (no per-sender mirrors needed: the reduced
               gradient is G + sum_r C(g_r - w_r), which updates G).
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.feedback import FEEDBACK_REGISTRY, FeedbackState
from repro.transport.base import shard_map_compat
from repro.transport.codecs import (WireCodec, _use_pallas_wire,
                                    fuse_payload, get_codec, unfuse_payload,
                                    wire_bytes)

# The modes whose registry entry admits the "dp" scope (core/feedback.py).
DP_FEEDBACK_MODES = tuple(m.name for m in FEEDBACK_REGISTRY.values()
                          if "dp" in m.scopes)


def _leaf_n(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def pack_grad_leaf(codec: WireCodec, a: jnp.ndarray, k_frac: float = 0.1):
    """One parameter leaf -> wire payload.  ``none`` passes the RAW leaf
    through (dtype-preserving: the uncompressed reduce stays bit-exact);
    lossy codecs flatten to ``(1, n)`` — one per-tensor scale per leaf, the
    q4 pad path for odd ``n``, uint16 TopK indices when ``n`` fits."""
    if codec.name == "none":
        return a
    return codec.pack(a.reshape(1, -1).astype(jnp.float32), k_frac)


def unpack_grad_leaf(codec: WireCodec, payload, shape) -> jnp.ndarray:
    """Inverse of :func:`pack_grad_leaf`; lossy codecs decode to f32."""
    if codec.name == "none":
        return payload
    n = _leaf_n(shape)
    return codec.unpack(payload, (1, n), jnp.float32).reshape(shape)


def grad_payload_structs(grads_like, codec_name: str,
                         k_frac: float = 0.1) -> List:
    """``eval_shape`` of every leaf's packed payload — the exact
    bytes-on-wire source for the benchmark's "dp" section."""
    codec = get_codec(codec_name)
    return [
        jax.eval_shape(lambda a: pack_grad_leaf(codec, a, k_frac),
                       jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
        for leaf in jax.tree.leaves(grads_like)
    ]


def dp_wire_report(grads_like, codec_name: str, *, k_frac: float = 0.1,
                   dp: int = 2) -> dict:
    """Exact and modeled wire bytes of ONE compressed DP all-reduce.

    ``payload_bytes_per_hop``: the fused uint8 buffer each replica sends
    per ring hop (exact, from the packed payload shapes).  ``model_bytes``:
    sum over leaves of ``n * wire_bytes_per_elem`` (q4/topk per-leaf
    raggedness included).  One reduce = ``dp - 1`` hops per replica.
    """
    codec = get_codec(codec_name)
    structs = grad_payload_structs(grads_like, codec_name, k_frac)
    exact = wire_bytes(structs)
    model = 0.0
    for leaf in jax.tree.leaves(grads_like):
        n = _leaf_n(leaf.shape)
        elem = (jnp.dtype(leaf.dtype).itemsize if codec.name == "none"
                else 2)
        model += codec.wire_bytes_per_elem(n, elem, k_frac) * n
    return {
        "dp_codec": codec_name, "k_frac": k_frac, "dp": dp,
        "n_param_leaves": len(structs),
        "n_payload_leaves": len(jax.tree.leaves(structs)),
        "payload_bytes_per_hop": exact,
        "model_bytes": round(model),
        "hops_per_reduce": dp - 1,
        "wire_bytes_per_reduce": (dp - 1) * exact,
    }


def init_dp_state(grads_like, dp: int, feedback: str = "none",
                  dtype=jnp.float32) -> FeedbackState:
    """Per-replica DP feedback state, carried in the train state (and the
    train-state checkpoint — exact-resume includes the residuals).

    A :class:`repro.core.feedback.FeedbackState` at scope ``"dp"``:
    ``resid`` holds ``(dp, *leaf)`` per-replica buffers (EF's error
    ``e_r`` / EF21's gradient model ``w_r``); ``agg`` is EF21's replicated
    aggregate ``G = sum_r w_r``.  ``mirror`` and unused slots are size-0
    placeholders so the pytree structure is mode-stable.
    """
    if feedback not in DP_FEEDBACK_MODES:
        raise ValueError(f"unknown dp feedback {feedback!r}; "
                         f"known: {DP_FEEDBACK_MODES}")
    z = jnp.zeros((0,), dtype)
    if feedback == "none":
        return FeedbackState(resid=jnp.zeros((dp, 0), dtype), mirror=z,
                             agg=z, scope="dp", direction="grad",
                             mode=feedback)
    resid = jax.tree.map(lambda a: jnp.zeros((dp, *a.shape), dtype),
                         grads_like)
    agg = (jax.tree.map(lambda a: jnp.zeros(a.shape, dtype), grads_like)
           if feedback == "ef21" else z)
    return FeedbackState(resid=resid, mirror=z, agg=agg, scope="dp",
                         direction="grad", mode=feedback)


def ring_exchange(axis: str, n: int, own, send_at=None) -> List:
    """Exchange payloads around a ``ppermute`` ring of ``n`` ranks.

    Returns ``[own, hop_1, ..., hop_{n-1}]``: hop ``h`` holds the payload
    of source rank ``(r - h) % n``, each entry its own array straight out
    of ``ppermute`` — the list is indexed by hop, which is static, so no
    buffer is ever written or read at a position computed from the traced
    rank.  :func:`by_source` puts decoded values in source-rank order.

    ``send_at=None`` is the all-gather: ``own`` travels the ring one rank
    a hop.  ``send_at(h)`` is the reduce-scatter: at hop ``h`` this rank
    sends ``send_at(h)`` straight to rank ``(r + h) % n``.
    """
    hops = [own]
    for h in range(1, n):
        if send_at is None:
            perm = [(i, (i + 1) % n) for i in range(n)]
            hops.append(jax.lax.ppermute(hops[-1], axis, perm))
        else:
            perm = [(i, (i + h) % n) for i in range(n)]
            hops.append(jax.lax.ppermute(send_at(h), axis, perm))
    return hops


def by_source(by_hop: List, axis: str) -> List:
    """Per-hop values (pytrees) from :func:`ring_exchange` -> the same
    values in source-rank order.  Entry ``s`` selects hop ``(r - s) % n``
    with ``lax.select_n`` on the decoded values, an elementwise op that
    XLA fuses into the decode; summing the entries in list order gives
    every rank the same association, so the same bits."""
    n = len(by_hop)
    if n == 1:
        return list(by_hop)
    r = jax.lax.axis_index(axis)
    pick = lambda s: lambda *hs: jax.lax.select_n((r - s) % n, *hs)
    return [jax.tree.map(pick(s), *by_hop) for s in range(n)]


def make_grad_all_reduce(mesh: Mesh, axis: str, codec: str = "none", *,
                         k_frac: float = 0.1, feedback: str = "none",
                         average: bool = False, fused: bool = True,
                         shard_axis: str = None, tp_axis: str = None,
                         tp_dims=None):
    """Build ``reduce(grads_dp, dp_state) -> (reduced, new_dp_state)``.

    ``grads_dp``: a gradient pytree whose leaves carry a leading replica
    dim ``(dp, *leaf)`` (e.g. the gradient w.r.t. dp-stacked pipeline
    params, or a ``vmap``-batched per-replica gradient).  The reduced
    gradient comes back replica-free and REPLICATED — every replica decodes
    the same payloads and sums them in the same order.

    ``average=True`` scales each replica's contribution by ``1/dp`` before
    compression (per-replica mean losses); default is a plain sum
    (per-replica losses already carry the global denominator).

    ``fused=False`` rings the raw per-leaf payload pytree instead of one
    fused buffer — same bytes, one collective launch PER PAYLOAD LEAF per
    hop; exists so the benchmark can audit the fusion claim.

    ``shard_axis``: on a 2D ``(data, stages)`` mesh, additionally shard
    the reduce over this axis — a leaf whose post-replica leading dim
    divides the axis (the stage-stacked layer gradients) rings only its
    own slice within its stage column, cutting per-device wire bytes by
    the stage count and avoiding the all-gather a stage-replicated spec
    would force on the (stage-sharded) pipeline gradient.  Non-divisible
    leaves degrade to stage-replicated.  Per-tensor scales then cover the
    per-stage slice (strictly finer, never coarser).

    ``tp_axis``/``tp_dims``: on a mesh with a tensor axis, a leaf whose
    ``tp_dims`` entry is >= 1 is tensor-SHARDED at that absolute dim
    (index into the ``(dp, *leaf)`` array; -1 = replicated over tensor —
    models/transformer.tp_param_dims shifted by the replica dim).  Each
    tensor coordinate then rings only its own weight shard over ``data``
    — the three rings never mix, and per-device DP wire bytes drop by
    ``tp`` for the sharded leaves.
    """
    if (tp_axis is None) != (tp_dims is None):
        raise ValueError("tp_axis and tp_dims come together (see "
                         "models/transformer.tp_param_dims)")
    if feedback not in DP_FEEDBACK_MODES:
        raise ValueError(f"unknown dp feedback {feedback!r}; "
                         f"known: {DP_FEEDBACK_MODES}")
    if feedback != "none" and codec == "none":
        raise ValueError("dp_feedback compensates a LOSSY dp_codec; "
                         "with dp_codec='none' there is nothing to "
                         "compensate — drop dp_feedback")
    codec_obj = get_codec(codec)
    dp = mesh.shape[axis]
    s_shard = mesh.shape[shard_axis] if shard_axis is not None else 1

    def _sharded(shape, lead: int) -> bool:
        """Does this leaf take the extra ``shard_axis`` dim after its
        ``lead`` replica dims?"""
        return (shard_axis is not None and len(shape) > lead
                and shape[lead] > 0 and shape[lead] % s_shard == 0)

    def body(g_dp, resid, agg):
        gl = [a[0] for a in jax.tree.leaves(g_dp)]
        gdef = jax.tree.structure(g_dp)
        if feedback != "none":
            rl = [a[0] for a in jax.tree.leaves(resid)]
        else:
            rl = [None] * len(gl)
        if feedback == "ef21":
            al = jax.tree.leaves(agg)
        else:
            al = [None] * len(gl)

        # -- compensate + pack (per leaf, f32 for lossy codecs) -------------
        xs, payloads = [], []
        for a, e in zip(gl, rl):
            if codec_obj.name == "none":
                x = (a / dp).astype(a.dtype) if average else a
            else:
                x = a.astype(jnp.float32)
                if average:
                    x = x / dp
                if feedback == "ef":
                    x = x + e
                elif feedback == "ef21":
                    x = x - e                     # resid holds w_r
            xs.append(x)
            payloads.append(pack_grad_leaf(codec_obj, x, k_frac))

        # -- exchange: one fused buffer (or the raw payload pytree) ---------
        struct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), payloads)
        hops = ring_exchange(axis, dp,
                             fuse_payload(payloads) if fused else payloads)

        # -- decode by hop + sum in source-rank order -----------------------
        # On the Pallas backend the whole receive side (unfuse -> dequant ->
        # rank-ordered accumulate) fuses into ONE kernel
        # (kernels/dp_reduce.py) when every leaf rides the per-tensor
        # q8/q4 wire format.  The fold is static and source-rank ordered
        # and every replica runs the identical program, so the reduced
        # gradient stays bitwise identical across replicas — same
        # association as the reference loop below (the per-element dequant
        # may round 1 ulp tighter where the compiler emits an FMA).
        plans = None
        if fused and codec_obj.name in ("q8", "q4") and _use_pallas_wire():
            from repro.kernels.dp_reduce import (build_decode_plans,
                                                 decode_fits,
                                                 decode_sum_fused)
            plans = build_decode_plans(struct, [g.shape for g in gl])
            if plans is not None and not decode_fits(plans, dp):
                plans = None
        if plans is not None:
            dense = decode_sum_fused(hops, plans, jax.lax.axis_index(axis))
            acc = [d.reshape(g.shape) for d, g in zip(dense, gl)]
        else:
            received = [payloads] + [unfuse_payload(b, struct) if fused
                                     else b for b in hops[1:]]
            decoded = [[unpack_grad_leaf(codec_obj, pls[i], g.shape)
                        for i, g in enumerate(gl)] for pls in received]
            acc = None
            for ms in by_source(decoded, axis):
                acc = ms if acc is None else [a + m for a, m in zip(acc, ms)]

        # -- feedback state updates (own decode == hop 0's, same bits) -----
        new_rl, new_al, out = [], [], []
        for i, g in enumerate(gl):
            if feedback == "none":
                out.append(acc[i].astype(g.dtype))
                continue
            m_own = unpack_grad_leaf(codec_obj, payloads[i], g.shape)
            if feedback == "ef":
                new_rl.append((xs[i] - m_own)[None])
                out.append(acc[i].astype(g.dtype))
            else:                                 # ef21
                reduced = al[i] + acc[i]          # G + sum_r C(g_r - w_r)
                new_rl.append((rl[i] + m_own)[None])
                new_al.append(reduced)
                out.append(reduced.astype(g.dtype))
        reduced_tree = jax.tree.unflatten(gdef, out)
        if feedback == "none":
            new_resid = jax.tree.map(lambda a: a, resid)
            new_agg = jax.tree.map(lambda a: a, agg)
        else:
            new_resid = jax.tree.unflatten(jax.tree.structure(resid),
                                           new_rl)
            new_agg = (jax.tree.unflatten(jax.tree.structure(agg), new_al)
                       if feedback == "ef21"
                       else jax.tree.map(lambda a: a, agg))
        return reduced_tree, new_resid, new_agg

    def _trace_wire(grads_dp) -> None:
        """Emit the DP-ring wire facts when tracing is on.  Runs at TRACE
        time (the ``reduce`` body executes once per jit compilation), so
        the steady-state step pays nothing and no device ops are added."""
        from repro.obs import trace
        tr = trace.get_tracer()
        if tr is None:
            return
        g_like = [jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                  for a in jax.tree.leaves(grads_dp)]
        rep = dp_wire_report(g_like, codec, k_frac=k_frac, dp=dp)
        tr.instant("dp.wire", cat="wire", axis=axis, feedback=feedback,
                   fused=fused, shard_axis=shard_axis or "",
                   launches_per_hop=(1 if fused
                                     else rep["n_payload_leaves"]),
                   **rep)

    def reduce(grads_dp, dp_state: FeedbackState):
        _trace_wire(grads_dp)

        def dp_spec(a, d=-1):
            e = [axis] + [None] * (a.ndim - 1)
            if _sharded(a.shape, 1):
                e[1] = shard_axis
            if tp_axis is not None and d >= 1:
                e[d] = tp_axis
            return P(*e)

        def out_spec(a, d=-1):
            e = [None] * max(a.ndim - 1, 0)
            if _sharded(a.shape, 1):
                e[0] = shard_axis
            if tp_axis is not None and d >= 1:
                e[d - 1] = tp_axis
            return P(*e)

        if tp_axis is None:
            gspec = jax.tree.map(dp_spec, grads_dp)
            ospec = jax.tree.map(out_spec, grads_dp)
        else:
            gspec = jax.tree.map(dp_spec, grads_dp, tp_dims)
            ospec = jax.tree.map(out_spec, grads_dp, tp_dims)
        if tp_axis is None or feedback == "none":
            rspec = jax.tree.map(dp_spec, dp_state.resid)
        else:
            # resid mirrors the grad tree leaf-for-leaf
            rspec = jax.tree.map(dp_spec, dp_state.resid, tp_dims)
        if tp_axis is None or feedback != "ef21":
            aspec = jax.tree.map(
                lambda a: P(shard_axis) if _sharded(a.shape, 0) else P(),
                dp_state.agg)
        else:
            aspec = jax.tree.map(
                lambda a, d: P(*[(shard_axis if (i == 0 and
                                                 _sharded(a.shape, 0))
                                  else (tp_axis if d >= 1 and i == d - 1
                                        else None))
                                 for i in range(a.ndim)]),
                dp_state.agg, tp_dims)
        reduced, new_resid, new_agg = shard_map_compat(
            body, mesh, (gspec, rspec, aspec),
            (ospec, rspec, aspec),
        )(grads_dp, dp_state.resid, dp_state.agg)
        return reduced, dp_state.replace(resid=new_resid, agg=new_agg)

    return reduce
