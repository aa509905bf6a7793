"""Compressed tensor-parallel collectives over the wire codecs.

The third communication axis: on a ``(data, stage, tensor)`` mesh the
attention/MLP weights shard over ``tensor`` (Megatron-style column/row
parallelism with the residual stream SEQUENCE-sharded, the Megatron-SP
layout), and what crosses the tensor ring is a PACKED payload from the
same wire-codec registry the stage boundaries and the DP gradient
all-reduce use (transport/codecs.py, fused uint8 framing via
kernels/framing.py):

  * activation path: an ALL-GATHER of the sequence-sharded residual
    before each sharded matmul group — every rank packs its ``(B, S/tp,
    d)`` shard, the payloads ride a ``ppermute`` ring (``tp - 1`` hops),
    and every rank decodes the ``tp`` payloads and concatenates them in
    source-rank order, so the gathered activation is bitwise identical on
    every rank;
  * gradient path: a REDUCE-SCATTER of the partial outputs / incoming
    activation-gradients — rank ``r`` packs the slice destined for each
    peer and sends it at ring distance ``h`` (``tp - 1`` single-hop
    permutes), then sums the ``tp`` decoded contributions for its own
    slice in source-rank order (fixed association).

Received payloads are kept by hop, a static index, and ordered by source
rank at decode (``collectives.ring_exchange`` / ``by_source``, shared
with the DP ring): the rank-dependent choice is a select on the decoded
values, never a uint8 buffer written or read at a traced position.

Both primitives are differentiable with the straight-through convention
the pipeline transport uses: the VJP of the compressed all-gather is the
compressed reduce-scatter of the incoming cotangent, and vice versa — so
activations compress forward and activation-gradients compress backward,
the paper's asymmetry, now on the tensor axis.

Error feedback (``FeedbackState(scope="tp")``, see
:func:`init_tp_state`) compensates the FORWARD all-gather (the
activation side, where the paper shows compensation matters most):

  * ``ef``   — send C(x + e);  e' = x + e - C(x + e)   (resid is
               sequence-sharded like x);
  * ``ef21`` — send the delta C(x - M_r) against a model M of every
               rank's shard; all ranks apply all decoded deltas, so M
               stays REPLICATED across the ring and the gathered
               activation IS the updated model (no separate resid).

``codec="none"`` is a RAW passthrough (dtype-preserving), so an
uncompressed TP program is bit-exact against a single-device reference
that applies the same rank-ordered partial-sum association.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.feedback import FEEDBACK_REGISTRY, FeedbackState
from repro.obs.trace import scope
from repro.transport.base import shard_map_compat
from repro.transport.codecs import (
    fuse_payload,
    get_codec,
    unfuse_payload,
    wire_bytes,
)
from repro.transport.collectives import (
    _leaf_n,
    by_source,
    pack_grad_leaf,
    ring_exchange,
    unpack_grad_leaf,
)

# The modes whose registry entry admits the "tp" scope (core/feedback.py).
TP_FEEDBACK_MODES = tuple(m.name for m in FEEDBACK_REGISTRY.values()
                          if "tp" in m.scopes)


def tp_payload_struct(shard_shape, codec_name: str, *, k_frac: float = 0.1,
                      dtype=jnp.bfloat16):
    """``eval_shape`` of one packed activation shard — the exact
    bytes-on-wire source for the benchmark's "tp" section."""
    codec = get_codec(codec_name)
    return jax.eval_shape(
        lambda a: pack_grad_leaf(codec, a, k_frac),
        jax.ShapeDtypeStruct(shard_shape, dtype))


def tp_wire_report(feat_shape, tp: int, codec_name: str, *,
                   k_frac: float = 0.1, dtype=jnp.bfloat16,
                   seq_dim: int = 1, sites: int = 1) -> dict:
    """Exact and modeled wire bytes of the TP collectives for one FULL
    activation of shape ``feat_shape`` (the sequence dim ``seq_dim``
    shards over the ring).

    Per collective (all-gather OR reduce-scatter) each rank sends
    ``tp - 1`` payloads of one packed ``(.., S/tp, ..)`` shard;
    ``payload_bytes_per_hop`` is exact (from the packed shapes),
    ``model_bytes`` is the codec's per-element cost model.  ``sites`` is
    the number of gather+scatter cut points a forward pass crosses (2 per
    sharded-matmul group: in-gather + out-scatter).
    """
    if feat_shape[seq_dim] % tp:
        raise ValueError(f"feat dim {seq_dim} ({feat_shape[seq_dim]}) "
                         f"not divisible by tp={tp}")
    codec = get_codec(codec_name)
    shard = list(feat_shape)
    shard[seq_dim] //= tp
    struct = tp_payload_struct(tuple(shard), codec_name, k_frac=k_frac,
                               dtype=dtype)
    exact = wire_bytes(struct)
    n = _leaf_n(shard)
    elem = jnp.dtype(dtype).itemsize if codec.name == "none" else 2
    model = codec.wire_bytes_per_elem(n, elem, k_frac) * n
    return {
        "tp_codec": codec_name, "k_frac": k_frac, "tp": tp,
        "shard_elems": n,
        "n_payload_leaves": len(jax.tree.leaves(struct)),
        "payload_bytes_per_hop": exact,
        "model_bytes": round(model),
        "hops_per_collective": tp - 1,
        "wire_bytes_per_collective": (tp - 1) * exact,
        "sites_per_forward": sites,
        "wire_bytes_per_forward": sites * 2 * (tp - 1) * exact,
    }


def init_tp_state(feat_shape, sites: int, feedback: str = "none",
                  dtype=jnp.float32) -> FeedbackState:
    """Per-site TP feedback state, carried in the train state.

    ``feat_shape`` is the FULL activation entering the layer stack
    (global batch — the batch dim shards over ``data``, the sequence dim
    over ``tensor``; activations are naturally batch-sharded so no
    replica stacking is needed).  ``sites`` counts the all-gather cut
    points per forward (2 per transformer block: attention + MLP
    in-gathers).  ``resid`` (EF) is sharded like the activations;
    ``mirror`` (EF21's model M) is replicated over the ring.
    """
    if feedback not in TP_FEEDBACK_MODES:
        raise ValueError(f"unknown tp feedback {feedback!r}; "
                         f"known: {TP_FEEDBACK_MODES}")
    z = jnp.zeros((0,), dtype)
    if feedback == "none":
        return FeedbackState(resid=z, mirror=z, agg=z, scope="tp",
                             direction="act", mode=feedback)
    buf = jnp.zeros((sites, *feat_shape), dtype)
    if feedback == "ef":
        return FeedbackState(resid=buf, mirror=z, agg=z, scope="tp",
                             direction="act", mode=feedback)
    return FeedbackState(resid=z, mirror=buf, agg=z, scope="tp",
                         direction="act", mode=feedback)


@dataclasses.dataclass
class TPCollectives:
    """The compressed TP wire for one mesh axis.

    Built once per train step (static config: codec, feedback, fusion);
    the differentiable :meth:`gather` / :meth:`scatter` close over the
    ring and are called from inside a ``shard_map`` body that binds
    ``axis`` (models/transformer.py's TP stage fn, via :func:`tp_apply`
    or the pipeline).  ``seq_dim`` is the activation dim sharded over the
    ring (1 for ``(B, S, d)``).
    """

    mesh: Mesh
    axis: str
    codec: str = "none"
    k_frac: float = 0.1
    feedback: str = "none"
    fused: bool = True
    seq_dim: int = 1

    def __post_init__(self):
        if self.feedback not in TP_FEEDBACK_MODES:
            raise ValueError(f"unknown tp feedback {self.feedback!r}; "
                             f"known: {TP_FEEDBACK_MODES}")
        if self.feedback != "none" and self.codec == "none":
            raise ValueError(
                "tp feedback compensates a LOSSY tp codec; with "
                "codec='none' there is nothing to compensate")
        self.tp = self.mesh.shape[self.axis]
        self._codec = get_codec(self.codec)
        self._gather_p = self._make_gather_p()
        self._scatter_p = self._make_scatter_p()

    # -- wire primitives (non-differentiable; called inside shard_map) -----

    def _pack(self, x):
        return pack_grad_leaf(self._codec, x, self.k_frac)

    def _decode(self, payload, shape, dtype):
        m = unpack_grad_leaf(self._codec, payload, shape)
        return m.astype(dtype)

    def _ring(self, own, shape, dtype, send_at=None):
        """Exchange packed payloads round the ring (:func:`ring_exchange`;
        ``send_at`` as there) -> their decoded values in source-rank
        order.  Each hop's buffer is unfused and decoded on its own; only
        the decoded values are put in order (:func:`by_source`, a select
        that XLA fuses into the decode), so no uint8 buffer is indexed by
        the traced rank."""
        struct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), own)
        wire = fuse_payload if self.fused else (lambda p: p)
        hops = ring_exchange(
            self.axis, self.tp, wire(own),
            None if send_at is None else lambda h: wire(send_at(h)))
        received = [own] + [unfuse_payload(b, struct) if self.fused else b
                            for b in hops[1:]]
        return by_source([self._decode(p, shape, dtype) for p in received],
                         self.axis)

    @scope("tensor_wire")
    def all_gather_wire(self, x_shard):
        """Ring all-gather of packed shards; every rank decodes each hop's
        payload and concatenates the ``tp`` values in source-rank order
        (bitwise identical output on every rank)."""
        if self.tp == 1:
            return x_shard
        parts = self._ring(self._pack(x_shard), x_shard.shape,
                           x_shard.dtype)
        return jnp.concatenate(parts, axis=self.seq_dim)

    @scope("tensor_wire")
    def reduce_scatter_wire(self, partial):
        """Packed-slice exchange + source-rank-ordered sum: rank ``r``
        keeps ``sum_s C(partial_s[slice r])``.  At hop ``h`` rank ``r``
        packs the slice of rank ``(r + h) % tp`` and sends it there.
        Every contribution — including the rank's own — goes through the
        codec, so the sum is uniformly compressed (same convention as the
        DP reduce)."""
        tp, dim = self.tp, self.seq_dim
        if tp == 1:
            return partial
        if partial.shape[dim] % tp:
            raise ValueError(f"reduce-scatter dim {dim} "
                             f"({partial.shape[dim]}) not divisible by "
                             f"tp={tp}")
        sl = partial.shape[dim] // tp
        r = jax.lax.axis_index(self.axis)

        def pack_for(h):
            return self._pack(jax.lax.dynamic_slice_in_dim(
                partial, ((r + h) % tp) * sl, sl, dim))

        shard_shape = list(partial.shape)
        shard_shape[dim] = sl
        out = None
        for m in self._ring(pack_for(0), tuple(shard_shape), partial.dtype,
                            pack_for):
            out = m if out is None else out + m
        return out

    # -- differentiable collectives (straight-through custom_vjp) ----------

    def _make_gather_p(self) -> Callable:
        """``gather_p(x_shard, add) -> full``: all-gather of
        ``C(x + add)`` (``add`` carries the stop-gradient feedback term);
        VJP = compressed reduce-scatter of the incoming cotangent."""

        @jax.custom_vjp
        def gather_p(x, add):
            return self.all_gather_wire(x + add)

        def fwd(x, add):
            return gather_p(x, add), None

        def bwd(_, dfull):
            dx = self.reduce_scatter_wire(dfull)
            return dx, jnp.zeros_like(dx)

        gather_p.defvjp(fwd, bwd)
        return gather_p

    def _make_scatter_p(self) -> Callable:
        """``scatter_p(partial) -> shard``: compressed reduce-scatter;
        VJP = compressed all-gather of the incoming cotangent."""

        @jax.custom_vjp
        def scatter_p(partial):
            return self.reduce_scatter_wire(partial)

        def fwd(partial):
            return scatter_p(partial), None

        def bwd(_, dshard):
            return (self.all_gather_wire(dshard),)

        scatter_p.defvjp(fwd, bwd)
        return scatter_p

    def _own_slice(self, full, sl: int):
        r = jax.lax.axis_index(self.axis)
        return jax.lax.dynamic_slice_in_dim(full, r * sl, sl, self.seq_dim)

    def gather(self, x_shard, resid=None, mirror=None):
        """Differentiable compressed all-gather with feedback.

        ``resid``/``mirror`` are ONE site's buffers (shape of the full /
        sharded activation, see :func:`init_tp_state`) or None.  Returns
        ``(full, new_resid, new_mirror)`` — state updates are
        stop-gradient (forward-only, like the pipeline's fw buffers).
        """
        sg = jax.lax.stop_gradient
        sl = x_shard.shape[self.seq_dim]
        if self.feedback == "none" or self.tp == 1:
            full = self._gather_p(x_shard, jnp.zeros_like(x_shard))
            return full, resid, mirror
        if self.feedback == "ef":
            e = resid.astype(x_shard.dtype)
            full = self._gather_p(x_shard, sg(e))
            own = self._own_slice(full, sl)
            new_resid = sg((x_shard + e - own).astype(resid.dtype))
            return full, new_resid, mirror
        # ef21: the wire carries the delta against the replicated model M;
        # the gathered activation IS the updated model.
        m_own = self._own_slice(mirror, sl).astype(x_shard.dtype)
        delta_full = self._gather_p(x_shard, sg(-m_own))
        full = mirror.astype(x_shard.dtype) + delta_full
        new_mirror = sg(full.astype(mirror.dtype))
        return full, resid, new_mirror

    def gather_site(self, x_shard, buf=None):
        """One cut point's :meth:`gather` with its single ACTIVE buffer
        (EF's resid / EF21's mirror / ignored for "none") — what the
        layer-stack loop threads per site."""
        if self.feedback == "ef":
            full, buf, _ = self.gather(x_shard, resid=buf)
        elif self.feedback == "ef21":
            full, _, buf = self.gather(x_shard, mirror=buf)
        else:
            full, _, _ = self.gather(x_shard)
        return full, buf

    def scatter(self, partial):
        """Differentiable compressed reduce-scatter (no feedback: the
        partial-output sum is the gradient-path twin of the DP reduce,
        which also runs codec-only)."""
        if self.tp == 1:
            return partial
        return self._scatter_p(partial)

    def wire_report(self, feat_shape, *, sites: int = 1,
                    dtype=jnp.bfloat16) -> dict:
        return tp_wire_report(feat_shape, self.tp, self.codec,
                              k_frac=self.k_frac, dtype=dtype,
                              seq_dim=self.seq_dim, sites=sites)


def _trace_wire(tpc: TPCollectives, feat_shape, dtype, sites: int) -> None:
    """Emit the TP-ring wire facts when tracing is on (trace time only —
    the body executes once per jit compilation)."""
    from repro.obs import trace
    tr = trace.get_tracer()
    if tr is None or tpc.tp == 1:
        return
    rep = tp_wire_report(feat_shape, tpc.tp, tpc.codec, k_frac=tpc.k_frac,
                         dtype=dtype, seq_dim=tpc.seq_dim, sites=sites)
    tr.instant("tp.wire", cat="wire", axis=tpc.axis, feedback=tpc.feedback,
               fused=tpc.fused,
               launches_per_hop=(1 if tpc.fused
                                 else rep["n_payload_leaves"]),
               **rep)


def tp_apply(fn: Callable, params, x, tpc: TPCollectives, *,
             param_dims, state: Optional[FeedbackState] = None,
             batch_axis: Optional[str] = None, sites: int = 0):
    """Run a TP stage function inside ``shard_map`` over the tensor ring.

    ``fn(params_local, x_local, resid_local, mirror_local) ->
    (y_local, new_resid, new_mirror)`` computes the layer stack on the
    sequence-sharded residual, calling ``tpc.gather``/``tpc.scatter`` at
    the cut points (models/transformer.py's ``tp_stage_stack_fn``).

    ``params``: the stack pytree — each leaf shards over the ring at the
    dim given by ``param_dims`` (a matching pytree of ints; -1 =
    replicated, e.g. norms, whose tiny gradients all-reduce via the
    shard_map transpose psum — the "all-reduce on the gradient path").
    When ``batch_axis`` is given (the DP x TP mesh) each leaf instead
    carries a LEADING broadcast replica dim ``(dp, ...)`` — its gradient
    comes back PER REPLICA for the compressed DP reduce — and ``x``'s
    batch dim shards over ``batch_axis``.

    ``state``: a scope-"tp" :class:`FeedbackState` (or None); returns
    ``(y, new_state)`` with ``y`` the reassembled full activation.
    """
    axis, seq_dim, tp = tpc.axis, tpc.seq_dim, tpc.tp
    if x.shape[seq_dim] % tp:
        raise ValueError(f"sequence dim {seq_dim} ({x.shape[seq_dim]}) "
                         f"not divisible by tp={tp}")
    if state is not None and state.scope != "tp":
        raise ValueError(f"tp_apply needs scope='tp' state, got "
                         f"{state.scope!r}")
    _trace_wire(tpc, x.shape, x.dtype, sites)

    lead = 1 if batch_axis is not None else 0

    def pspec(a, d):
        spec = [None] * a.ndim
        if batch_axis is not None:
            spec[0] = batch_axis
        if d >= 0:
            spec[d + lead] = axis
        return P(*spec)

    x_spec = P(*[batch_axis if i == 0 else (axis if i == seq_dim else None)
                 for i in range(x.ndim)])

    def st_spec(a, sharded: bool):
        if a.ndim != x.ndim + 1:          # size-0 placeholder
            return P(*([None] * a.ndim))
        inner = [batch_axis if i == 0 else
                 (axis if (i == seq_dim and sharded) else None)
                 for i in range(x.ndim)]
        return P(None, *inner)

    if state is None:
        state = init_tp_state(x.shape, max(sites, 1), "none")
    rspec = jax.tree.map(lambda a: st_spec(a, True), state.resid)
    mspec = jax.tree.map(lambda a: st_spec(a, False), state.mirror)

    def body(p, xs, rs, ms):
        if batch_axis is not None:
            p = jax.tree.map(lambda a: a[0], p)
        y, nr, nm = fn(p, xs, rs, ms)
        return y, nr, nm

    p_specs = jax.tree.map(pspec, params, param_dims)
    y, new_resid, new_mirror = shard_map_compat(
        body, tpc.mesh,
        (p_specs, x_spec, rspec, mspec),
        (x_spec, rspec, mspec),
    )(params, x, state.resid, state.mirror)
    return y, state.replace(resid=new_resid, mirror=new_mirror)
