"""Real pipeline parallelism with compressed, DIFFERENTIABLE stage handoffs.

The stage boundary is an actual ``jax.lax.ppermute`` over a mesh axis inside
``shard_map`` — microbatched pipelining, each device holding one (or, with
the interleaved schedule, several virtual) stage slices.  The boundary
tensor is PACKED by a wire codec (transport/codecs.py) before the ppermute,
so the collective-permute bytes in the lowered HLO shrink by exactly the
paper's compression ratio.

Training-capable: the packed hop is wrapped in ``jax.custom_vjp`` whose
backward ppermutes a *packed gradient payload* in the REVERSE direction,
compressed by the boundary policy's ``bw`` compressor — the paper's
simultaneous activation + gradient compression, on real wire formats.
With ``reuse_indices`` (paper Table 5) the forward TopK indices ride in the
residuals on both ends of the wire: the backward payload is VALUES ONLY
(gathered with the receiver's indices, scattered with the sender's), saving
the index bytes in the gradient direction.

Scheduling is a first-class, pluggable layer (transport/schedules.py):
``gpipe`` (minimum-tick skew scan, the original semantics), ``1f1b``
(rematerialized ticks + fused single-buffer hops, for
``microbatches >> stages``), and ``interleaved`` (v virtual stage slices
per device, round-robin: the fill bubble shrinks by 1/v while every one of
the ``v*S - 1`` cuts is a compressed wire cut).  The scan body below is
entirely plan-driven — a :class:`~repro.transport.schedules.Schedule` maps
``(tick, device)`` to (virtual chunk, microbatch, validity, inject/emit
points), and the same custom_vjp hop serves every schedule.

Error feedback (paper Sec. 2.4/2.5, Tables 3-4) over the real wire:
per-stage EF / EF21 / EF-mixed / AQ-SGD buffers ride the ``lax.scan`` carry,
sharded ``P(axis)`` so each device owns the buffers of the cuts it sends
across (one per virtual chunk).  What gets packed onto the wire is the
COMPENSATED message:

  * EF        — payload = pack(x + e); the receiver's unpack IS m = C(x+e).
  * EF-mixed  — two half-K payloads, pack(x, K/2) + pack(e, K/2).
  * EF21      — payload = pack(x - g), a compressed delta; the receiver
                reconstructs m = g + unpack(payload) from a local MIRROR of
                the sender's buffer (both start at zero and apply identical
                deltas, so they never diverge — the AQ-SGD system design).
  * AQ-SGD    — per-example EF21: the ``(num_samples, *feat)`` buffer is
                gathered/scattered by the example ids of the microbatch in
                flight, on both the sender and the receiver mirror.

The backward hop symmetrically applies ``bw_feedback`` to the gradient
payload.  Backward-direction buffers are only touched during backprop, so
their updates are delivered AS THE COTANGENT of the ``bw_state`` argument —
the same functional-state trick core/boundary.py uses (take ``grad`` w.r.t.
``bw_state`` in the train step and read the new buffers out of the gradient
pytree).  Buffer rows are per-example, hence disjoint across microbatches:
each scan step contributes exactly one (masked) slice and the cotangent sum
over steps reassembles the full updated buffer.

Gradients retrace exactly the valid pipeline paths (the fill/drain garbage
paths get zero cotangent through the plan's masks; ring hops that carry
garbage — e.g. the wrap-around cut under gpipe — are explicitly ignored by
both directions, while under the interleaved schedule the wrap hop carries
the real chunk-boundary payload).
"""
from __future__ import annotations

from typing import Callable, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.feedback import (FeedbackState, gather_rows, get_mode,
                                 needs_recv_mirror, scatter_rows)
from repro.core.policy import (BoundaryPolicy, quant_policy, topk_policy)
from repro.obs.trace import scope
from repro.transport.base import Transport, shard_map_compat as _shard_map
from repro.transport.codecs import codec_for, fuse_payload, unfuse_payload
from repro.transport.schedules import Schedule, as_schedule


SCHEME_POLICIES = {
    "none": lambda k: BoundaryPolicy(),
    "q8": lambda k: quant_policy(8, 8),
    "q4": lambda k: quant_policy(4, 4),
    "topk": lambda k: topk_policy(k),
    "topk_reuse": lambda k: topk_policy(k, reuse_indices=True),
}


def _policy_for_scheme(scheme: str, k_frac: float) -> BoundaryPolicy:
    try:
        return SCHEME_POLICIES[scheme](k_frac)
    except KeyError:
        raise ValueError(f"unknown scheme {scheme!r}; "
                         f"known: {sorted(SCHEME_POLICIES)}") from None


def _zeros_f0(x):
    """float0 cotangent for an integer/bool primal (custom_vjp contract)."""
    return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# Feedback state
# ---------------------------------------------------------------------------

def init_feedback_state(policy: BoundaryPolicy, feat_shape, *,
                        num_stages: int, batch: int,
                        microbatches: Optional[int] = None,
                        num_samples: int = 0, dtype=jnp.float32,
                        virtual_stages: int = 1, dp: int = 1):
    """Per-stage feedback buffers for the real pipeline.

    Returns ``{"fw": FeedbackState, "bw": FeedbackState}`` whose ``resid``
    (the sender-side buffer) / ``mirror`` (the receiver-side replica of
    the delta-coded modes) arrays carry leading dim ``num_stages`` (shard
    ``P(axis)``: device d's slice holds the buffers of the cuts it owns —
    cut d for ``resid`` / the mirror of cut d-1; with ``virtual_stages=v``
    a chunk dim follows, slot k being cut ``k*S + d`` / its mirror).

    Global modes (ef/ef21/efmixed) keep ``(S, [v,] mb, B/(mb*dp), *feat)``
    — the simulated ``(B, *feat)`` buffer split by microbatch; AQ-SGD
    keeps ``(S, [v,] num_samples/dp, *feat)``.  Unused buffers are size-0
    placeholders ``(S, 0)`` so the pytree structure is policy-stable.

    ``dp > 1`` (the 2D ``(data, stages)`` mesh) prepends a replica dim —
    shard ``P(data_axis, stage_axis)``: each replica row compensates its
    own contiguous batch shard exactly as a solo run on that shard would,
    and AQ-SGD's dataset-indexed buffer shards BY EXAMPLE ID (replica r
    owns rows ``[r*num_samples/dp, (r+1)*num_samples/dp)``; see
    :func:`repro.core.feedback.shard_ids` for the data-routing contract).
    """
    mb = microbatches or num_stages
    if batch % (mb * dp):
        raise ValueError(f"batch {batch} not divisible by microbatches "
                         f"{mb} x dp {dp}")
    mbsz = batch // (mb * dp)
    v = virtual_stages
    chunk = () if v == 1 else (v,)
    rep = () if dp == 1 else (dp,)

    def buf(mode: str, mirror: bool):
        if mode == "none" or (mirror and not needs_recv_mirror(mode)):
            return jnp.zeros((*rep, num_stages, 0), dtype)
        if get_mode(mode).per_example:
            assert num_samples > 0, "aqsgd needs the dataset size"
            if num_samples % dp:
                raise ValueError(
                    f"aqsgd + dp shards the per-example buffer by id: "
                    f"num_samples {num_samples} must be divisible by "
                    f"dp {dp}")
            return jnp.zeros(
                (*rep, num_stages, *chunk, num_samples // dp, *feat_shape),
                dtype)
        return jnp.zeros((*rep, num_stages, *chunk, mb, mbsz, *feat_shape),
                         dtype)

    def fbs(mode: str, direction: str) -> FeedbackState:
        return FeedbackState(
            resid=buf(mode, False), mirror=buf(mode, True),
            agg=jnp.zeros((0,), dtype), scope="boundary",
            direction=direction, mode=mode)

    return {"fw": fbs(policy.feedback, "fw"),
            "bw": fbs(policy.bw_feedback, "bw")}


def _empty_state(num_stages: int, dtype, direction: str,
                 dp: int = 1) -> FeedbackState:
    rep = () if dp == 1 else (dp,)
    z = jnp.zeros((*rep, num_stages, 0), dtype)
    return FeedbackState(resid=z, mirror=z, agg=jnp.zeros((0,), dtype),
                         scope="boundary", direction=direction, mode="none")


class PipelineTransport(Transport):
    """The real wire at one stage cut: packed ``ppermute`` both directions.

    ``fw``/``bw`` are SPMD collectives — they must run inside a
    ``shard_map`` over ``axis``.  :func:`pipeline_apply` composes them into
    a ``custom_vjp`` so the backward hop runs during backprop, with
    feedback buffers threaded through the scan carry (fw) and through
    cotangents (bw).

    ``fused=True`` (the 1f1b/interleaved default) bitcasts each hop's
    payload pytree into ONE contiguous uint8 buffer before the ppermute —
    byte-identical on the wire, one collective launch per direction per
    tick instead of one per payload leaf.
    """

    def __init__(self, policy: BoundaryPolicy, axis: str, num_stages: int,
                 *, virtual_stages: int = 1, fused: bool = False):
        if policy.reuse_indices and (policy.feedback != "none"
                                     or policy.bw_feedback != "none"):
            raise NotImplementedError(
                f"reuse_indices=True conflicts with feedback="
                f"{policy.feedback!r} / bw_feedback={policy.bw_feedback!r} "
                "on the real pipeline: the backward payload is values-only, "
                "gathered at the forward TopK indices — but a compensated "
                "message C(x + e) keeps different coordinates than C(x), "
                "so those indices no longer address the wire message. "
                "Valid configurations: (a) reuse_indices=True with "
                "feedback='none' and bw_feedback='none' (paper Table 5), "
                "or (b) feedback/bw_feedback modes with "
                "reuse_indices=False (paper Tables 3-4).")
        for mode, comp, nm in ((policy.feedback, policy.fw, "fw"),
                               (policy.bw_feedback, policy.bw, "bw")):
            if mode == "efmixed" and comp.kind != "topk":
                raise ValueError(f"EF-mixed needs a TopK {nm} compressor")
        self.policy = policy
        self.axis = axis
        self.num_stages = num_stages
        self.virtual_stages = virtual_stages
        self.fused = fused
        self._fw_codec = codec_for(policy.fw)
        self._bw_codec = codec_for(policy.bw)
        self.perm_fw = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        self.perm_bw = [(i, (i - 1) % num_stages) for i in range(num_stages)]

    def _hop(self, payload, perm):
        """One ring hop of a packed payload: plain per-leaf ppermute, or a
        single fused byte-buffer launch."""
        if not self.fused:
            return jax.lax.ppermute(payload, self.axis, perm)
        struct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), payload)
        moved = jax.lax.ppermute(fuse_payload(payload), self.axis, perm)
        return unfuse_payload(moved, struct)

    # -- wire framing (shared with benchmarks: eval_shape-able) -------------

    def pack_fw_message(self, y, buf_slice):
        """Compensated forward payload + the local decode m (what the
        receiver will see) + the new send-buffer slice."""
        p, kf = self.policy, self.policy.fw.k_frac
        pack = self._fw_codec.pack
        unpack = lambda pl: self._fw_codec.unpack(pl, y.shape, y.dtype)
        if p.feedback == "none":
            payload = pack(y, kf)
            return payload, None, buf_slice
        if p.feedback == "ef":
            xe = y + buf_slice.astype(y.dtype)
            payload = pack(xe, kf)
            m = unpack(payload)
            return payload, m, xe - m
        if p.feedback == "efmixed":
            e = buf_slice.astype(y.dtype)
            payload = {"x": pack(y, kf / 2.0), "e": pack(e, kf / 2.0)}
            m = (self._fw_codec.unpack(payload["x"], y.shape, y.dtype)
                 + self._fw_codec.unpack(payload["e"], y.shape, y.dtype))
            return payload, m, (y + e) - m
        # delta-coded: ef21 / aqsgd — wire carries C(x - buf) only
        b = buf_slice.astype(y.dtype)
        payload = pack(y - b, kf)
        return payload, None, b + unpack(payload)

    def unpack_fw_message(self, moved, shape, dtype, recv_slice):
        """Receiver-side decode of :meth:`pack_fw_message`'s payload.
        Returns (message, new recv-mirror slice or None)."""
        p = self.policy
        if p.feedback in ("none", "ef"):
            return self._fw_codec.unpack(moved, shape, dtype), None
        if p.feedback == "efmixed":
            return (self._fw_codec.unpack(moved["x"], shape, dtype)
                    + self._fw_codec.unpack(moved["e"], shape, dtype)), None
        m = recv_slice.astype(dtype) + self._fw_codec.unpack(moved, shape,
                                                             dtype)
        return m, m

    def pack_bw_message(self, g, buf_slice):
        """Compensated gradient payload + new bw send-buffer slice."""
        p, kb = self.policy, self.policy.bw.k_frac
        pack = self._bw_codec.pack
        unpack = lambda pl: self._bw_codec.unpack(pl, g.shape, g.dtype)
        if p.bw_feedback == "none":
            return pack(g, kb), buf_slice
        if p.bw_feedback == "ef":
            ge = g + buf_slice.astype(g.dtype)
            payload = pack(ge, kb)
            return payload, ge - unpack(payload)
        if p.bw_feedback == "efmixed":
            e = buf_slice.astype(g.dtype)
            payload = {"g": pack(g, kb / 2.0), "e": pack(e, kb / 2.0)}
            m = (self._bw_codec.unpack(payload["g"], g.shape, g.dtype)
                 + self._bw_codec.unpack(payload["e"], g.shape, g.dtype))
            return payload, (g + e) - m
        b = buf_slice.astype(g.dtype)                       # ef21
        payload = pack(g - b, kb)
        return payload, b + unpack(payload)

    def unpack_bw_message(self, moved, shape, dtype, recv_slice):
        p = self.policy
        if p.bw_feedback in ("none", "ef"):
            return self._bw_codec.unpack(moved, shape, dtype), None
        if p.bw_feedback == "efmixed":
            return (self._bw_codec.unpack(moved["g"], shape, dtype)
                    + self._bw_codec.unpack(moved["e"], shape, dtype)), None
        m = recv_slice.astype(dtype) + self._bw_codec.unpack(moved, shape,
                                                             dtype)
        return m, m

    def fw_payload_struct(self, x_struct, buf_struct=None):
        """eval_shape of the forward wire payload (feedback framing incl.)
        — the benchmark's exact bytes-on-wire source."""
        buf = buf_struct or jax.ShapeDtypeStruct(x_struct.shape,
                                                 x_struct.dtype)
        return jax.eval_shape(lambda y, b: self.pack_fw_message(y, b)[0],
                              x_struct, buf)

    def bw_payload_struct(self, g_struct, buf_struct=None):
        buf = buf_struct or jax.ShapeDtypeStruct(g_struct.shape,
                                                 g_struct.dtype)
        return jax.eval_shape(lambda g, b: self.pack_bw_message(g, b)[0],
                              g_struct, buf)

    # -- SPMD hops ----------------------------------------------------------

    def fw(self, x, fw_buf=None, ids=None):
        """Plain (feedback-free) hop: pack x, ppermute to the next stage,
        unpack.  ``ctx`` carries the (sent, received) TopK indices when
        ``reuse_indices`` is set."""
        payload = self._fw_codec.pack(x, self.policy.fw.k_frac)
        moved = self._hop(payload, self.perm_fw)
        out = self._fw_codec.unpack(moved, x.shape, x.dtype)
        ctx = None
        if self.policy.reuse_indices:
            ctx = (payload["idx"], moved["idx"])
        return out, fw_buf, ctx

    @scope("stage_wire")
    def fw_hop(self, y, fw_st, meta):
        """Feedback-compensated forward hop inside the pipeline scan.

        ``fw_st``: this device's local {"resid","mirror"} buffers (one
        :class:`~repro.core.feedback.FeedbackState` slice); ``meta``: the
        tick's bookkeeping pytree — clipped microbatch indices
        (``jc_s``/``jc_r``: send / receive side), virtual chunk indices
        (``ks``/``kr``), AQ-SGD example ids (``ids_s``/``ids_r``) and
        validity masks (``vs``/``vr``) from the schedule's plan.
        """
        mode = self.policy.feedback
        if mode == "none":
            out, _, ctx = self.fw(y)
            return out, fw_st, ctx
        v = self.virtual_stages
        send_sl = gather_rows(fw_st["resid"], meta["ks"], meta["jc_s"],
                              meta["ids_s"], mode, v)
        payload, _, new_send = self.pack_fw_message(y, send_sl)
        moved = self._hop(payload, self.perm_fw)
        recv_sl = (gather_rows(fw_st["mirror"], meta["kr"], meta["jc_r"],
                               meta["ids_r"], mode, v)
                   if needs_recv_mirror(mode) else None)
        out, new_recv = self.unpack_fw_message(moved, y.shape, y.dtype,
                                               recv_sl)
        new_st = {
            "resid": scatter_rows(fw_st["resid"], meta["ks"], meta["jc_s"],
                                  meta["ids_s"], mode, v,
                                  new_send, send_sl, meta["vs"]),
            "mirror": (fw_st["mirror"] if new_recv is None else
                       scatter_rows(fw_st["mirror"], meta["kr"],
                                    meta["jc_r"], meta["ids_r"], mode, v,
                                    new_recv, recv_sl, meta["vr"])),
        }
        return out, new_st, None

    def bw(self, g, bw_buf=None, ctx=None):
        """Plain backward hop: pack the activation-gradient, ppermute to
        the PREVIOUS stage, unpack.  With ``reuse_indices`` the payload is
        values only."""
        if self.policy.reuse_indices:
            idx_sent, idx_recv = ctx
            b = g.shape[0]
            gflat = g.reshape(b, -1)
            vals = jnp.take_along_axis(
                gflat, idx_recv.astype(jnp.int32), axis=-1
            ).astype(jnp.bfloat16)
            vals_back = jax.lax.ppermute(vals, self.axis, self.perm_bw)
            from repro.core.compressors import topk_scatter
            g_out = topk_scatter(vals_back.astype(jnp.float32),
                                 idx_sent.astype(jnp.int32), g.shape,
                                 jnp.float32).astype(g.dtype)
            return g_out, bw_buf
        payload = self._bw_codec.pack(g, self.policy.bw.k_frac)
        moved = self._hop(payload, self.perm_bw)
        return self._bw_codec.unpack(moved, g.shape, g.dtype), bw_buf

    @scope("stage_wire")
    def bw_hop(self, g, bw_send_sl, bw_recv_sl, meta, ctx):
        """Feedback-compensated backward hop (runs inside ``send``'s VJP).

        Device d sends the gradient of its RECEIVED activation (the cut
        below the chunk it computes NEXT tick — slot ``[kr, jc_r]``,
        buffer slice ``bw_send_sl``) and receives the gradient of its SENT
        activation (cut ``[ks, jc_s]``, mirror slice ``bw_recv_sl``).
        Returns ``(g_y, new_send_sl, new_recv_sl)`` where the slice
        updates are masked cotangent CONTRIBUTIONS (zero on invalid steps
        — the per-step sum reassembles the buffer).
        """
        mode = self.policy.bw_feedback
        if mode == "none" or self.policy.reuse_indices:
            g_y, _ = self.bw(g, ctx=ctx)
            new_send = jnp.zeros_like(bw_send_sl)
            new_recv = jnp.zeros_like(bw_recv_sl)
        else:
            payload, new_send = self.pack_bw_message(g, bw_send_sl)
            moved = self._hop(payload, self.perm_bw)
            g_y, new_recv = self.unpack_bw_message(
                moved, g.shape, g.dtype,
                bw_recv_sl if needs_recv_mirror(mode) else None)
            new_send = jnp.where(meta["vr"], new_send,
                                 0.0).astype(bw_send_sl.dtype)
            new_recv = (jnp.zeros_like(bw_recv_sl) if new_recv is None else
                        jnp.where(meta["vs"], new_recv, 0.0).astype(
                            bw_recv_sl.dtype))
        # Without feedback a garbage-path payload is C(0) = 0 and dies on
        # its own; a COMPENSATED message is C(0 + e) != 0 — the buffer
        # leaks onto fill/drain paths and garbage ring hops.  Mask the
        # received gradient by this tick's own validity (``vs``: the
        # microbatch whose gradient lands here) and by not being the LAST
        # LOGICAL STAGE (whose real cotangent comes from the loss through
        # ``outs``, never from the ring).
        g_y = jnp.where(meta["vs"] & ~meta["last"], g_y, jnp.zeros_like(g_y))
        return g_y, new_send, new_recv

    def make_send(self, fw_template=None) -> Callable:
        """``send(y, fw_st, bw_send_sl, bw_recv_sl, meta)``: the
        differentiable wire hop — fw hop in the primal (returning the
        updated fw buffers for the scan carry), bw hop on the cotangent
        (returning the bw buffer updates as the cotangents of the
        ``bw_*_sl`` slice arguments).

        ``fw_template``: ShapeDtypeStructs of the local fw state (for zero
        cotangents) — default size-0 (no feedback).  ``meta`` is the
        integer/bool bookkeeping pytree from the schedule plan; its
        cotangents are float0.
        """
        transport = self
        fw_template = fw_template or {
            "resid": jax.ShapeDtypeStruct((0,), jnp.float32),
            "mirror": jax.ShapeDtypeStruct((0,), jnp.float32)}

        @jax.custom_vjp
        def send(y, fw_st, bw_send_sl, bw_recv_sl, meta):
            out, new_fw, _ = transport.fw_hop(y, fw_st, meta)
            return out, new_fw

        def send_fwd(y, fw_st, bw_send_sl, bw_recv_sl, meta):
            out, new_fw, ctx = transport.fw_hop(y, fw_st, meta)
            # residuals stay O(slice): never the full fw buffers
            return (out, new_fw), (bw_send_sl, bw_recv_sl, ctx, meta)

        def send_bwd(res, cots):
            bw_send_sl, bw_recv_sl, ctx, meta = res
            g, _g_new_fw = cots          # fw buffers are forward-only state
            g_y, new_bw_send, new_bw_recv = transport.bw_hop(
                g, bw_send_sl, bw_recv_sl, meta, ctx)
            zero_fw = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                   fw_template)
            return (g_y, zero_fw, new_bw_send, new_bw_recv,
                    jax.tree.map(_zeros_f0, meta))

        send.defvjp(send_fwd, send_bwd)
        return send


# ---------------------------------------------------------------------------
# Differentiable pipelined apply over a mesh axis
# ---------------------------------------------------------------------------

def wire_telemetry(transport: "PipelineTransport", sched: Schedule,
                   feat_shape, dtype, *, microbatches: int,
                   dp: int = 1) -> dict:
    """Host-side wire facts of one pipeline configuration: the chosen
    codecs, EXACT payload bytes per hop (``eval_shape`` of the packed
    wire message — the same source benchmarks/pipeline_wire.py audits),
    and collective launches per tick.  Pure trace-time Python: no device
    ops, shared by the tracer instrumentation and the benchmark's
    telemetry-vs-cost-model assertion."""
    from repro.transport.codecs import wire_bytes
    x_s = jax.ShapeDtypeStruct(tuple(feat_shape), dtype)
    fw_pl = transport.fw_payload_struct(x_s)
    if transport.policy.reuse_indices:
        # backward hop ppermutes VALUES ONLY (bf16, forward k) — the
        # reused indices already sit at both ends of the wire
        n = int(np.prod(feat_shape[1:]))
        k = max(1, int(round(transport.policy.fw.k_frac * n)))
        bw_pl = jax.ShapeDtypeStruct((feat_shape[0], k), jnp.bfloat16)
    else:
        bw_pl = transport.bw_payload_struct(x_s)
    s = transport.num_stages
    return {
        "axis": transport.axis, "stages": s,
        "virtual_stages": transport.virtual_stages,
        "schedule": sched.name, "microbatches": microbatches, "dp": dp,
        "fw_codec": transport.policy.fw.name,
        "bw_codec": transport.policy.bw.name,
        "feedback": transport.policy.feedback,
        "fw_payload_bytes_per_hop": wire_bytes(fw_pl),
        "bw_payload_bytes_per_hop": wire_bytes(bw_pl),
        "launches_per_fw_hop": (1 if transport.fused
                                else len(jax.tree.leaves(fw_pl))),
        "launches_per_bw_hop": (1 if transport.fused
                                else len(jax.tree.leaves(bw_pl))),
        "wire_cuts": sched.wire_cuts(s),
    }


def _trace_wire(transport, sched, feat_shape, dtype, mb, dp) -> None:
    """Emit the wire-telemetry event when tracing is on.  Runs at TRACE
    time (once per compilation), so the steady-state step pays nothing."""
    from repro.obs import trace
    tr = trace.get_tracer()
    if tr is None:
        return
    tr.instant("pipeline.wire", cat="wire",
               **wire_telemetry(transport, sched, feat_shape, dtype,
                                microbatches=mb, dp=dp))


def pipeline_apply(stage_fn: Callable, params_stacked, x, mesh: Mesh,
                   axis: str, *, policy: Optional[BoundaryPolicy] = None,
                   scheme: Optional[str] = None, k_frac: float = 0.1,
                   microbatches: Optional[int] = None,
                   schedule: Union[str, Schedule] = "gpipe",
                   virtual_stages: Optional[int] = None,
                   fw_state=None, bw_state=None, ids=None,
                   dp_axis: Optional[str] = None,
                   tp_axis: Optional[str] = None, tp_param_dims=None,
                   seq_dim: int = 1):
    """Run ``stage_fn(stage_params, x) -> x`` as a pipelined stage stack
    over mesh axis ``axis``, ppermute-ing PACKED payloads between stages —
    differentiable end to end (compressed gradient payloads hop backward).

    params_stacked: pytree with leading dim ``S * v`` in LOGICAL stage
    order (one slice per stage; ``v = virtual_stages``, 1 unless the
    schedule is interleaved).  Logical stage ``l`` runs on device
    ``l % S`` (round-robin), so with ``v == 1`` slice ``s`` simply lives
    on device ``s``.  x: (B, ...) global batch.  ``policy`` (a
    :class:`BoundaryPolicy`) or ``scheme`` (a codec name) selects the wire
    format; every cut uses the same policy (SPMD: one program).

    ``schedule`` picks the pipeline schedule (``"gpipe"`` | ``"1f1b"`` |
    ``"interleaved"``, or a :class:`~repro.transport.schedules.Schedule`
    instance); ``microbatches`` defaults to the stage count and must be
    positive when given (the interleaved schedule additionally requires it
    to be a multiple of S).

    ``dp_axis``: run ``dp = mesh.shape[dp_axis]`` data-parallel replicas of
    the pipeline on a 2D ``(data, stages)`` mesh.  ``params_stacked`` then
    carries a LEADING replica dim ``(dp, S * v, ...)`` — one (usually
    broadcast) copy per replica, so its gradient comes back PER REPLICA
    with no hidden cross-replica ``psum``; the caller reduces it explicitly
    (transport/collectives.py, the compressed DP gradient all-reduce).
    The global batch splits into ``dp`` contiguous shards (replica r takes
    ``x[r*B/dp:(r+1)*B/dp]``), each pipelined with ``microbatches``
    microbatches exactly as a solo run on that shard would be.

    ``tp_axis``: run every stage tensor-parallel over a third mesh axis
    (the 3D ``(data, stage, tensor)`` mesh).  ``stage_fn`` must then be
    TP-aware (models/transformer.tp_stage_stack_fn closed over a
    :class:`~repro.transport.tp_collectives.TPCollectives` on the same
    axis): it receives the SEQUENCE-SHARDED microbatch (dim ``seq_dim``
    of the per-microbatch activation split over ``tp_axis``) plus the
    tp-local weight shards (``tp_param_dims``: pytree matching
    ``params_stacked`` of per-leaf sharded-dim indices, -1 = replicated
    — models/transformer.tp_param_dims).  The stage-boundary payload is
    then the shard, so the three rings stay separable: stage hops move
    ``1/tp`` of each cut, TP gathers ring within a stage, and the DP
    reduce rings over ``data``.  Boundary feedback buffers are not
    supported on this path (their addressing assumes full-sequence
    slots); pass a buffer-free policy.

    Feedback state: when the policy carries EF/EF21/EF-mixed/AQ-SGD
    buffers, pass ``fw_state``/``bw_state`` from
    :func:`init_feedback_state` (built with the same ``virtual_stages``,
    and ``ids``: (B,) example ids for AQ-SGD).  The return value becomes
    ``(out, new_fw_state)`` and the updated backward buffers arrive as the
    COTANGENT of ``bw_state`` (take ``grad`` w.r.t. it — see
    train/steps.py).  Passing size-0 state with ``feedback='none'`` is
    allowed (it rides the carry untouched), so the calling convention can
    be policy-independent.
    """
    if policy is None:
        policy = _policy_for_scheme(scheme or "none", k_frac)
    s_stages = mesh.shape[axis]
    dp = mesh.shape[dp_axis] if dp_axis is not None else 1
    tp = mesh.shape[tp_axis] if tp_axis is not None else 1
    if tp_axis is not None:
        if policy.needs_fw_buffer or policy.needs_bw_buffer:
            raise ValueError(
                f"policy {policy.name!r} carries boundary feedback "
                "buffers; the tensor-parallel pipeline path supports "
                "buffer-free boundary policies only")
        if tp_param_dims is None:
            raise ValueError("tp_axis needs tp_param_dims (see "
                             "models/transformer.tp_param_dims)")
        if x.shape[seq_dim] % tp:
            raise ValueError(f"sequence dim {seq_dim} ({x.shape[seq_dim]})"
                             f" not divisible by tp={tp}")
    sched = as_schedule(schedule, virtual_stages)
    v = sched.virtual_stages
    transport = PipelineTransport(policy, axis, s_stages,
                                  virtual_stages=v, fused=sched.fused_wire)

    if microbatches is None:
        mb = s_stages
    else:
        if not isinstance(microbatches, (int, np.integer)) \
                or microbatches <= 0:
            raise ValueError(
                "microbatches must be a positive int, got "
                f"{microbatches!r} — pass None (or omit it) to default to "
                "the stage count")
        mb = int(microbatches)
    sched.validate(mb, s_stages)
    b = x.shape[0]
    if b % (mb * dp):
        raise ValueError(f"batch {b} is not divisible by microbatch count "
                         f"{mb} x dp {dp} (microbatches defaults to the "
                         "stage count)")
    mbsz = b // (mb * dp)

    lead = {a.shape[0] for a in jax.tree.leaves(params_stacked)}
    slice_dim = 1 if dp_axis is not None else 0
    want_lead = dp if dp_axis is not None else s_stages * v
    slices = ({a.shape[1] for a in jax.tree.leaves(params_stacked)}
              if dp_axis is not None else lead)
    if lead != {want_lead} or slices != {s_stages * v}:
        got = (f"got leading dims {sorted(lead)}" if dp_axis is None else
               f"got replica dims {sorted(lead)} (want {dp}) x slice dims "
               f"{sorted(slices)}")
        raise ValueError(
            "params_stacked must have leading dim"
            f"{(' (dp=' + str(dp) + ',') if dp_axis else ''} num_stages * "
            f"virtual_stages = {s_stages}*{v} = {s_stages * v}"
            f"{')' if dp_axis else ''} (logical stage slices); {got}")
    if v > 1:
        # logical order -> device-major order: device d's contiguous block
        # (rows d*v .. d*v+v-1 under the P(axis) shard) holds its chunks
        # k = 0..v-1, i.e. logical stages d, d+S, ..., d+(v-1)S.
        order = np.array([k * s_stages + d
                          for d in range(s_stages) for k in range(v)])
        params_dev = jax.tree.map(
            lambda a: jnp.take(a, order, axis=slice_dim), params_stacked)
    else:
        params_dev = params_stacked

    with_state = fw_state is not None or bw_state is not None
    if (policy.needs_fw_buffer or policy.needs_bw_buffer) and not with_state:
        raise ValueError(
            f"policy {policy.name!r} carries feedback buffers: pass "
            "fw_state/bw_state from init_feedback_state()")
    state_dp = dp if dp_axis is not None else 1
    if fw_state is None:
        fw_state = _empty_state(s_stages, x.dtype, "fw", dp=state_dp)
    if bw_state is None:
        bw_state = _empty_state(s_stages, x.dtype, "bw", dp=state_dp)
    for st, nm in ((fw_state, "fw_state"), (bw_state, "bw_state")):
        if st.resid.size and st.resid.shape[0] != \
                (state_dp if dp_axis is not None else s_stages):
            raise ValueError(
                f"{nm} was built for a different mesh: expected leading "
                f"{'(dp, stages)' if dp_axis is not None else '(stages,)'} "
                f"dims {(state_dp, s_stages) if dp_axis is not None else (s_stages,)}, "
                f"got shape {st.resid.shape} — rebuild with "
                f"init_feedback_state(..., dp={state_dp})")
    if ids is None:
        ids = jnp.zeros((b,), jnp.int32)
    rep = (dp,) if dp_axis is not None else ()
    ids_mb = ids.reshape(*rep, mb, mbsz).astype(jnp.int32)

    x_mb = x.reshape(*rep, mb, mbsz, *x.shape[1:])
    feat_shape = x_mb.shape[len(rep) + 1:]
    if tp_axis is not None:
        # the stage boundary carries the sequence SHARD: every ring's
        # payload (and the scan buffer) is 1/tp of the full cut
        local = list(feat_shape)
        local[seq_dim] //= tp
        feat_shape = tuple(local)
    _trace_wire(transport, sched, feat_shape, x.dtype, mb, dp)

    # the scan carry / shard_map threading works on plain {resid, mirror}
    # dicts (the per-direction slices of the FeedbackState; ``agg`` is
    # dp-scope-only and stays outside the pipeline)
    fw_c = {"resid": fw_state.resid, "mirror": fw_state.mirror}
    bw_c = {"resid": bw_state.resid, "mirror": bw_state.mirror}
    strip = 2 if dp_axis is not None else 1
    # AQ-SGD + dp: the (num_samples/dp, *feat) id-shard is addressed with
    # LOCAL rows — each replica row subtracts its shard offset from the
    # global example ids (core.feedback.shard_ids routing contract)
    per_example = (policy.needs_fw_buffer
                   and get_mode(policy.feedback).per_example)
    ns_shard = (fw_state.resid.shape[strip + (1 if v > 1 else 0)]
                if per_example else 0)

    local_fw = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[strip:], a.dtype), fw_c)
    send = transport.make_send(local_fw)
    bw_mode = policy.bw_feedback
    stage = jax.checkpoint(stage_fn) if sched.remat_ticks else stage_fn
    n_steps = sched.num_ticks(mb, s_stages)

    def body(params_local, x_local, fw_st, bw_st, ids_all):
        # params_local: this device's chunk stack (leading dim v);
        # x_local: (mb, ...).  Under dp_axis each carries one extra
        # leading replica dim of size 1 (this device's replica shard).
        if dp_axis is not None:
            params_local = jax.tree.map(lambda a: a[0], params_local)
            x_local = x_local[0]
            ids_all = ids_all[0]
            fw_st = jax.tree.map(lambda a: a[0], fw_st)
            bw_st = jax.tree.map(lambda a: a[0], bw_st)
            if per_example:
                replica = jax.lax.axis_index(dp_axis)
                ids_all = (ids_all
                           - (replica * ns_shard).astype(ids_all.dtype))
        if v == 1:
            params_local = jax.tree.map(lambda a: a[0], params_local)
        fw_st = jax.tree.map(lambda a: a[0], fw_st)
        bw_st = jax.tree.map(lambda a: a[0], bw_st)
        idx = jax.lax.axis_index(axis)
        buf = jnp.zeros(feat_shape, x_local.dtype)
        outs = jnp.zeros_like(x_local)

        def step(carry, t):
            buf, outs, fw_st = carry
            pl = sched.plan(t, idx, mb, s_stages)       # compute/send side
            pn = sched.plan(t + 1, idx, mb, s_stages)   # next tick's input
            # logical stage 0 injects from the host batch; everyone else
            # consumes the payload that arrived on the ring last tick
            x_in = jnp.where(pl.inject, x_local[pl.jc], buf)
            p_t = (params_local if v == 1 else
                   jax.tree.map(lambda a: a[pl.k], params_local))
            y = stage(p_t, x_in)
            meta = {"jc_s": pl.jc, "jc_r": pn.jc, "ks": pl.k, "kr": pn.k,
                    "ids_s": ids_all[pl.jc], "ids_r": ids_all[pn.jc],
                    "vs": pl.valid, "vr": pn.valid, "last": pl.last}
            # bw buffer slices gather OUTSIDE send: their cotangents
            # scatter-add the per-step updates back into the full buffers
            bss = (bw_st["resid"] if bw_mode == "none"
                   else gather_rows(bw_st["resid"], pn.k, pn.jc,
                                    meta["ids_r"], bw_mode, v))
            brs = (bw_st["mirror"] if not needs_recv_mirror(bw_mode)
                   else gather_rows(bw_st["mirror"], pl.k, pl.jc,
                                    meta["ids_s"], bw_mode, v))
            buf, fw_st = send(y, fw_st, bss, brs, meta)
            # the LAST LOGICAL STAGE's valid y is a pipeline output
            outs = jnp.where(pl.last & pl.valid, outs.at[pl.jc].set(y), outs)
            return (buf, outs, fw_st), None

        (_, outs, fw_st), _ = jax.lax.scan(
            step, (buf, outs, fw_st), jnp.arange(n_steps))
        # only the LAST device (of each replica row) holds the pipeline
        # output; return it stage-stacked (out_specs P(axis)) so the
        # global slice [-1] is exactly that device's buffer —
        # transposition-unambiguous (the cotangent lands on device S-1
        # alone, no psum involved).
        outs = outs[None] if dp_axis is None else outs[None, None]
        expand = ((lambda a: a[None]) if dp_axis is None
                  else (lambda a: a[None, None]))
        return outs, jax.tree.map(expand, fw_st)

    lead_axes = (axis,) if dp_axis is None else (dp_axis, axis)
    ids_spec = P() if dp_axis is None else P(dp_axis)
    st_axes = P(*lead_axes)
    if tp_axis is None:
        pspec = jax.tree.map(lambda _: st_axes, params_dev)
        x_spec = ids_spec
        out_spec = P(axis) if dp_axis is None else P(axis, dp_axis)
    else:
        def leaf_spec(a, d):
            entries = [None] * a.ndim
            for i, nm in enumerate(lead_axes):
                entries[i] = nm
            if d >= 0:
                entries[d] = tp_axis
            return P(*entries)
        pspec = jax.tree.map(leaf_spec, params_dev, tp_param_dims)
        xe = [None] * x_mb.ndim
        if dp_axis is not None:
            xe[0] = dp_axis
        xe[len(rep) + 1 + seq_dim] = tp_axis
        x_spec = P(*xe)
        oe = [None] * (x_mb.ndim + 1)
        oe[0] = axis
        if dp_axis is not None:
            oe[1] = dp_axis
        oe[2 + len(rep) + seq_dim] = tp_axis
        out_spec = P(*oe)
    st_spec = lambda st: jax.tree.map(lambda _: st_axes, st)
    # jit even when called eagerly: differentiating an eager shard_map
    # evaluates its primal half op by op, and JAX then rejects the
    # replicated sharding XLA gives the zero-size residuals (the empty
    # feedback buffers) against their stage-sharded out_specs.  Inside
    # a jitted caller this jit is inlined.
    out, new_fw = jax.jit(_shard_map(
        body, mesh,
        (pspec, x_spec, st_spec(fw_c), st_spec(bw_c), ids_spec),
        (out_spec, st_spec(fw_c)),
    ))(params_dev, x_mb, fw_c, bw_c, ids_mb)
    out = out[-1].reshape(b, *x.shape[1:])
    if with_state:
        return out, fw_state.replace(resid=new_fw["resid"],
                                     mirror=new_fw["mirror"])
    return out


def pipeline_forward(stage_fn, params_stacked, x, mesh, axis, *,
                     scheme: str = "none", k_frac: float = 0.1,
                     microbatches: Optional[int] = None,
                     schedule: Union[str, Schedule] = "gpipe",
                     virtual_stages: Optional[int] = None):
    """Original forward-only entry point (now differentiable too): the
    scheme compresses BOTH directions symmetrically."""
    return pipeline_apply(stage_fn, params_stacked, x, mesh, axis,
                          scheme=scheme, k_frac=k_frac,
                          microbatches=microbatches, schedule=schedule,
                          virtual_stages=virtual_stages)
