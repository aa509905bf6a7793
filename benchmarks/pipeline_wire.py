"""Bytes-on-wire of the REAL compressed pipeline, forward AND backward.

The differentiable pipeline (repro/transport/pipeline.py) ppermutes a packed
payload forward (activations) and a packed payload backward (activation-
gradients).  This benchmark measures both per wire scheme:

  * exact payload bytes per hop (from the packed pytree's shapes/dtypes),
    ASSERTED against each codec's ``wire_bytes_per_elem`` cost model to
    within per-tensor-scale overhead;
  * collective-permute bytes in the compiled HLO of the forward-only and
    the value_and_grad programs — the compression ratio visible in the
    collective roofline term;
  * a per-SCHEDULE section (gpipe / 1f1b / interleaved): analytic bubble
    fraction, per-microbatch wire bytes across all cuts, and the compiled
    collective-permute LAUNCH count — asserting interleaved's smaller
    bubble and that the fused 1F1B hop at most halves steady-state
    launches.

Run:
  PYTHONPATH=src python -m benchmarks.pipeline_wire          # 4-stage, GPT-2ish
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    # 8 devices: the 4-stage pipeline sections use 4, the 3D
    # (data=2, stage=2, tensor=2) ring audit needs all 8
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json

import jax
import jax.numpy as jnp

from repro.launch.dryrun import collective_bytes
from repro.launch.mesh import make_mesh


def payload_bytes(scheme: str, feat_shape, k_frac: float):
    """(fw, bw, fw_model, bw_model) bytes for ONE pipeline hop.

    fw/bw are exact packed-payload bytes (eval_shape, no compute);
    fw_model/bw_model come from the codec cost model (excl. scales).
    """
    from repro.transport.codecs import wire_bytes
    from repro.transport.pipeline import (PipelineTransport,
                                          _policy_for_scheme)
    policy = _policy_for_scheme(scheme, k_frac)
    transport = PipelineTransport(policy, "stage", 4)
    x = jax.ShapeDtypeStruct(feat_shape, jnp.bfloat16)
    fw_payload = jax.eval_shape(
        lambda a: transport._fw_codec.pack(a, policy.fw.k_frac), x)
    fw = wire_bytes(fw_payload)
    n = 1
    for s in feat_shape[1:]:
        n *= s
    if policy.reuse_indices:
        # backward payload is values only (indices already at both ends);
        # its length is the FORWARD pack's k — the reused indices
        k = max(1, int(round(policy.fw.k_frac * n)))
        bw = feat_shape[0] * k * 2
    else:
        bw_payload = jax.eval_shape(
            lambda a: transport._bw_codec.pack(a, policy.bw.k_frac), x)
        bw = wire_bytes(bw_payload)
    fw_model, bw_model = transport.wire_bytes_per_example(n, elem_bytes=2)
    return fw, bw, fw_model * feat_shape[0], bw_model * feat_shape[0]


def feedback_payload_bytes(feedback: str, bw_feedback: str, feat_shape,
                           k_frac: float, num_samples: int = 64):
    """(fw, bw, fw_model, bw_model) bytes for one hop under an
    error-feedback mode (TopK compressors, paper Tables 3-4).

    The compensated message costs the SAME wire bytes as the plain
    compressor — EF packs x+e (one payload), EF-mixed packs two half-K
    payloads, EF21/AQ-SGD pack the delta — which is asserted against the
    feedback-free codec cost model below.
    """
    from repro.core.policy import BoundaryPolicy
    from repro.core.compressors import topk
    from repro.transport.codecs import wire_bytes
    from repro.transport.pipeline import PipelineTransport
    import jax.numpy as jnp
    policy = BoundaryPolicy(fw=topk(k_frac), bw=topk(k_frac),
                            feedback=feedback, bw_feedback=bw_feedback)
    transport = PipelineTransport(policy, "stage", 4)
    x = jax.ShapeDtypeStruct(feat_shape, jnp.bfloat16)
    fw = wire_bytes(transport.fw_payload_struct(x))
    bw = wire_bytes(transport.bw_payload_struct(x))
    n = 1
    for s in feat_shape[1:]:
        n *= s
    fw_model, bw_model = transport.wire_bytes_per_example(n, elem_bytes=2)
    return fw, bw, fw_model * feat_shape[0], bw_model * feat_shape[0]


def measure_feedback(modes=(("none", "none"), ("ef", "ef"),
                            ("ef21", "ef21"), ("efmixed", "efmixed"),
                            ("aqsgd", "none")), *, batch=8, seq=256,
                     d_model=256, stages=4, k_frac=0.10,
                     check: bool = True):
    """Per-feedback-mode fw+bw payload bytes (AQ-SGD message vs plain
    TopK), asserted against the codec cost models: error compensation is
    wire-cost-free."""
    mb_feat = (batch // stages, seq, d_model)
    reports = []
    for fb, bw_fb in modes:
        fw, bw, fw_model, bw_model = feedback_payload_bytes(
            fb, bw_fb, mb_feat, k_frac)
        if check:
            # slack: per-tensor scales + the max(1, round(k/2 * n))
            # rounding of EF-mixed's two half-K payloads
            slack = 64 + 0.005 * max(fw_model, 1)
            assert abs(fw - fw_model) <= slack, (fb, fw, fw_model)
            slack = 64 + 0.005 * max(bw_model, 1)
            assert abs(bw - bw_model) <= slack, (bw_fb, bw, bw_model)
        reports.append({
            "feedback": fb, "bw_feedback": bw_fb, "scheme": "topk",
            "k_frac": k_frac, "fw_payload_bytes": fw,
            "bw_payload_bytes": bw, "fw_model_bytes": round(fw_model),
            "bw_model_bytes": round(bw_model),
        })
    return reports


def measure_schedules(*, stages=4, batch=16, seq=32, d_model=64, d_ff=128,
                      mb=8, v=2, scheme="q8", k_frac=0.10,
                      check: bool = True):
    """Per-schedule report (ISSUE 3): analytic bubble fraction, collective-
    permute LAUNCH count of the compiled fw+bw program, and fw+bw payload
    bytes per microbatch (per-hop payload x wire cuts).

    The scan body lowers ONCE into the while loop, so the HLO launch count
    IS the per-steady-state-tick launch count (x2: one fw loop, one bw
    loop, plus O(1) ops outside).  Asserted here:

      * interleaved (v) bubble fraction < GPipe's — (S-1)/(v*mb+S-1) vs
        (S-1)/(mb+S-1);
      * the fused 1F1B hop at most HALVES steady-state collective
        launches vs the same schedule unfused (q8 payloads: the codes +
        min + scale leaves ride one byte buffer instead of three
        collectives per direction).
    """
    import dataclasses
    from repro.launch.dryrun import collective_counts
    from repro.transport.pipeline import pipeline_apply
    from repro.transport.schedules import get_schedule
    n_dev = jax.device_count()
    assert n_dev >= stages, (n_dev, stages)
    mesh = make_mesh((stages,), ("stage",))
    key = jax.random.PRNGKey(0)

    def stage_fn(p, h):
        return h + (jax.nn.gelu((h @ p["w1"]).astype(jnp.float32))
                    .astype(jnp.bfloat16) @ p["w2"])

    def params_struct(n_slices):
        return {
            "w1": jax.ShapeDtypeStruct((n_slices, d_model, d_ff),
                                       jnp.bfloat16),
            "w2": jax.ShapeDtypeStruct((n_slices, d_ff, d_model),
                                       jnp.bfloat16),
        }

    x = jax.ShapeDtypeStruct((batch, seq, d_model), jnp.bfloat16)

    def launches(sched, n_slices):
        def loss(p, xx):
            out = pipeline_apply(stage_fn, p, xx, mesh, "stage",
                                 scheme=scheme, k_frac=k_frac,
                                 microbatches=mb, schedule=sched)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        hlo = jax.jit(jax.grad(loss)).lower(
            params_struct(n_slices), x).compile().as_text()
        return collective_counts(hlo).get("collective-permute", 0)

    mb_feat = (batch // mb, seq, d_model)
    fw_hop, bw_hop, _, _ = payload_bytes(scheme, mb_feat, k_frac)
    configs = [
        (get_schedule("gpipe"), stages),
        (get_schedule("1f1b"), stages),
        (get_schedule("interleaved", v), stages * v),
    ]
    reports = []
    for sched, n_slices in configs:
        rep = sched.describe(mb, stages)
        rep.update({
            "scheme": scheme, "stages": stages, "microbatches": mb,
            "collective_permute_launches": launches(sched, n_slices),
            "fw_payload_bytes_per_hop": fw_hop,
            "bw_payload_bytes_per_hop": bw_hop,
            "fw_wire_bytes_per_microbatch":
                fw_hop * sched.wire_cuts(stages),
            "bw_wire_bytes_per_microbatch":
                bw_hop * sched.wire_cuts(stages),
        })
        reports.append(rep)
    unfused = dataclasses.replace(get_schedule("1f1b"), fused_wire=False)
    unfused_launches = launches(unfused, stages)
    reports[1]["collective_permute_launches_unfused"] = unfused_launches
    if check:
        by = {r["schedule"]: r for r in reports}
        assert (by["interleaved"]["bubble_fraction"]
                < by["gpipe"]["bubble_fraction"]), reports
        fused_launches = by["1f1b"]["collective_permute_launches"]
        assert fused_launches * 2 <= unfused_launches, (
            fused_launches, unfused_launches)
    return reports


def measure(schemes=("none", "q8", "q4", "topk", "topk_reuse"), *, stages=4,
            batch=8, seq=256, d_model=256, d_ff=1024, k_frac=0.10,
            check: bool = True):
    """One report per scheme: exact fw/bw payload bytes per hop (checked
    against the codec cost model) + compiled-HLO collective-permute bytes
    for the forward and the grad program."""
    from repro.transport.pipeline import pipeline_apply
    n_dev = jax.device_count()
    assert n_dev >= stages, (n_dev, stages)
    mesh = make_mesh((stages,), ("stage",))

    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    params = {
        "w1": (jax.random.normal(k1, (stages, d_model, d_ff), jnp.float32)
               * (1 / d_model) ** 0.5).astype(jnp.bfloat16),
        "w2": (jax.random.normal(k2, (stages, d_ff, d_model), jnp.float32)
               * (1 / d_ff) ** 0.5).astype(jnp.bfloat16),
    }

    def stage_fn(p, h):
        return h + (jax.nn.gelu((h @ p["w1"]).astype(jnp.float32))
                    .astype(jnp.bfloat16) @ p["w2"])

    x = jax.ShapeDtypeStruct((batch, seq, d_model), jnp.bfloat16)
    params_s = jax.eval_shape(lambda: params)
    mb_feat = (batch // stages, seq, d_model)

    reports = []
    for scheme in schemes:
        def run(p, xx):
            return pipeline_apply(stage_fn, p, xx, mesh, "stage",
                                  scheme=scheme, k_frac=k_frac)

        def loss(p, xx):
            return jnp.sum(run(p, xx).astype(jnp.float32) ** 2)

        fw_hlo = collective_bytes(
            jax.jit(run).lower(params_s, x).compile().as_text()
        ).get("collective-permute", 0)
        grad_hlo = collective_bytes(
            jax.jit(jax.grad(loss)).lower(params_s, x).compile().as_text()
        ).get("collective-permute", 0)

        fw, bw, fw_model, bw_model = payload_bytes(scheme, mb_feat, k_frac)
        if check:
            # cost model holds to within per-tensor-scale overhead
            # (min/scale scalars, one q4 pad nibble column)
            slack = 64 + 0.005 * max(fw_model, 1)
            assert abs(fw - fw_model) <= slack, (scheme, fw, fw_model)
            slack = 64 + 0.005 * max(bw_model, 1)
            assert abs(bw - bw_model) <= slack, (scheme, bw, bw_model)

        reports.append({
            "scheme": scheme, "stages": stages, "k_frac": k_frac,
            "fw_payload_bytes": fw, "bw_payload_bytes": bw,
            "fw_model_bytes": round(fw_model), "bw_model_bytes": round(bw_model),
            "hlo_fw_collective_permute_bytes": fw_hlo,
            "hlo_fwbw_collective_permute_bytes": grad_hlo,
            "fw_ratio_vs_none": None, "bw_ratio_vs_none": None,
        })
    base_fw = reports[0]["fw_payload_bytes"] or 1
    base_bw = reports[0]["bw_payload_bytes"] or 1
    for r in reports:
        r["fw_ratio_vs_none"] = round(base_fw / max(r["fw_payload_bytes"], 1),
                                      2)
        r["bw_ratio_vs_none"] = round(base_bw / max(r["bw_payload_bytes"], 1),
                                      2)
    return reports


def measure_dp(codecs=("none", "q8", "q4", "topk"), *, dp=2, stages=2,
               d_model=64, d_ff=128, k_frac=0.10, check: bool = True):
    """Per-dp-codec report for the compressed DP gradient all-reduce
    (transport/collectives.py) on the 2D ``(data, stages)`` mesh:

      * exact fused payload bytes per ring hop (from the packed payload
        shapes, per-leaf per-tensor scales and the q4 pad/ragged-TopK
        paths included), ASSERTED against the codec's
        ``wire_bytes_per_elem`` cost model;
      * wire bytes per reduce per replica = ``(dp - 1)`` hops x payload;
      * collective-permute LAUNCH counts of the compiled reduce, fused
        (one uint8 buffer per hop) vs unfused (one launch per payload
        leaf per hop) — asserting the fusion at most halves launches
        whenever payloads are multi-leaf;
      * for q8: the DATA-RING launch count inside a full 2D DPxPP train
        step, split from the stage ring by the collective's
        source-target pairs (the ``collective_counts(by_pairs=True)``
        audit from launch/dryrun.py).
    """
    from repro.launch.dryrun import collective_counts
    from repro.launch.mesh import make_dp_pipeline_mesh
    from repro.transport.collectives import (dp_wire_report, init_dp_state,
                                             make_grad_all_reduce)
    from repro.transport.pipeline import pipeline_apply
    mesh = make_dp_pipeline_mesh(dp, stages)
    grads_like = {
        "w1": jax.ShapeDtypeStruct((stages, d_model, d_ff), jnp.float32),
        "w2": jax.ShapeDtypeStruct((stages, d_ff, d_model), jnp.float32),
        "gamma": jax.ShapeDtypeStruct((33,), jnp.float32),   # odd/ragged
    }
    grads_dp = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((dp, *s.shape), s.dtype), grads_like)

    def launches(codec, fused):
        fn = make_grad_all_reduce(mesh, "data", codec, k_frac=k_frac,
                                  fused=fused)
        st = init_dp_state(grads_like, dp, "none")
        hlo = jax.jit(fn).lower(
            grads_dp, jax.eval_shape(lambda: st)).compile().as_text()
        return collective_counts(hlo).get("collective-permute", 0)

    def dp_ring_pairs():
        """The data-axis ring's source-target pair signature on this
        mesh: within each stage column, replica r sends to r+1."""
        dev = mesh.devices
        pairs = set()
        for j in range(stages):
            for r in range(dp):
                pairs.add((int(dev[r, j].id), int(dev[(r + 1) % dp, j].id)))
        return pairs

    def train_step_ring_launches():
        """collective-permute launches along the DATA axis inside one
        compiled 2D train step (toy pipeline + fused q8 DP reduce)."""
        reduce_fn = make_grad_all_reduce(mesh, "data", "q8", k_frac=k_frac)

        def stage_fn(p, h):
            return h + (jax.nn.gelu((h @ p["w1"]).astype(jnp.float32))
                        .astype(jnp.bfloat16) @ p["w2"])

        def step(params, dp_state, x):
            pdp = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (dp, *a.shape)), params)

            def loss(p):
                y = pipeline_apply(stage_fn, p, x, mesh, "stage",
                                   scheme="q8", dp_axis="data")
                return jnp.sum(y.astype(jnp.float32) ** 2)
            g = jax.grad(loss)(pdp)
            return reduce_fn(g, dp_state)

        params = {
            "w1": jax.ShapeDtypeStruct((stages, d_model, d_ff),
                                       jnp.bfloat16),
            "w2": jax.ShapeDtypeStruct((stages, d_ff, d_model),
                                       jnp.bfloat16),
        }
        st = init_dp_state(params, dp, "none")
        x = jax.ShapeDtypeStruct((8, d_model), jnp.bfloat16)
        hlo = jax.jit(step).lower(
            params, jax.eval_shape(lambda: st), x).compile().as_text()
        ring = dp_ring_pairs()
        data_ring, stage_ring = 0, 0
        for key, n in collective_counts(hlo, by_pairs=True).items():
            op, _, pairs_s = key.partition("|")
            if op != "collective-permute" or not pairs_s.startswith("{"):
                continue
            pairs = {tuple(int(v) for v in p.split(","))
                     for p in pairs_s[2:-2].split("},{")}
            if pairs <= ring:
                data_ring += n
            else:
                stage_ring += n
        return data_ring, stage_ring

    reports = []
    for codec in codecs:
        rep = dp_wire_report(grads_like, codec, k_frac=k_frac, dp=dp)
        rep["collective_permute_launches"] = launches(codec, True)
        rep["collective_permute_launches_unfused"] = launches(codec, False)
        if check:
            # cost model holds to within per-leaf scale overhead (+ the
            # q4 pad nibble / TopK k-rounding per ragged leaf)
            slack = 16 * rep["n_param_leaves"] \
                + 0.005 * max(rep["model_bytes"], 1)
            assert abs(rep["payload_bytes_per_hop"]
                       - rep["model_bytes"]) <= slack, rep
            assert rep["collective_permute_launches"] == dp - 1, rep
            if rep["n_payload_leaves"] > rep["n_param_leaves"]:
                assert (rep["collective_permute_launches"] * 2
                        <= rep["collective_permute_launches_unfused"]), rep
        reports.append(rep)
    data_ring, stage_ring = train_step_ring_launches()
    reports.append({
        "dp_codec": "q8", "section": "2d_train_step_audit", "dp": dp,
        "stages": stages,
        "data_ring_collective_permute_launches": data_ring,
        "stage_ring_collective_permute_launches": stage_ring,
    })
    if check:
        # the fused DP reduce adds exactly dp-1 data-axis launches to the
        # whole train step; the stage ring keeps its own (scan-looped) hops
        assert data_ring == dp - 1, reports[-1]
        assert stage_ring >= 1, reports[-1]
    return reports


def measure_tp(codecs=("none", "q8", "q4", "topk"), *, tp=2, batch=4,
               seq=256, d_model=256, d_ff=512, k_frac=0.10,
               check: bool = True):
    """Per-tp-codec report for the compressed tensor-parallel collectives
    (transport/tp_collectives.py) on the ``tensor`` ring:

      * exact packed payload bytes of one sequence shard per ring hop
        (``tp_wire_report``), ASSERTED against the codec's
        ``wire_bytes_per_elem`` cost model;
      * collective-permute LAUNCH count of one compiled ``tp_apply``
        forward with a single gather+scatter site — the fused framing
        rings ONE buffer per hop, so the count is exactly
        ``2 * (tp - 1)``;
      * a 2x2x2 ``(data, stage, tensor)`` train step:
        ``collective_counts(by_pairs=True)`` buckets every permute
        launch into the three rings via ``obs.probes.ring_pairs`` —
        asserting the rings never mix (no unclassified launches) and
        each carries its own traffic.
    """
    from repro.launch.dryrun import collective_counts
    from repro.launch.mesh import make_3d_mesh, make_tensor_mesh
    from repro.obs.probes import ring_pairs
    from repro.transport.collectives import (init_dp_state,
                                             make_grad_all_reduce)
    from repro.transport.pipeline import pipeline_apply
    from repro.transport.tp_collectives import (TPCollectives, tp_apply,
                                                tp_wire_report)
    mesh = make_tensor_mesh(tp)
    feat = (batch, seq, d_model)
    # GLOBAL weight shapes: tp_apply/pipeline_apply slice the sharded dim
    params_s = {
        "w1": jax.ShapeDtypeStruct((d_model, d_ff), jnp.bfloat16),
        "w2": jax.ShapeDtypeStruct((d_ff, d_model), jnp.bfloat16),
    }
    x_s = jax.ShapeDtypeStruct(feat, jnp.bfloat16)

    def launches(codec):
        tpc = TPCollectives(mesh, "tensor", codec=codec, k_frac=k_frac)

        def stage_fn(p, h, resid, mirror):
            full = tpc.gather(h)[0]
            part = (jax.nn.gelu((full @ p["w1"]).astype(jnp.float32))
                    .astype(jnp.bfloat16) @ p["w2"])
            return h + tpc.scatter(part), resid, mirror

        def run(p, xx):
            y, _ = tp_apply(stage_fn, p, xx, tpc,
                            param_dims={"w1": 1, "w2": 0}, sites=1)
            return y

        hlo = jax.jit(run).lower(params_s, x_s).compile().as_text()
        return collective_counts(hlo).get("collective-permute", 0)

    reports = []
    for codec in codecs:
        rep = tp_wire_report(feat, tp, codec, k_frac=k_frac, sites=1)
        rep["collective_permute_launches_fw"] = launches(codec)
        if check:
            # cost model holds to within per-tensor-scale overhead
            slack = 64 + 0.005 * max(rep["model_bytes"], 1)
            assert abs(rep["payload_bytes_per_hop"]
                       - rep["model_bytes"]) <= slack, rep
            assert rep["collective_permute_launches_fw"] == 2 * (tp - 1), rep
        reports.append(rep)

    # -- 2x2x2 three-ring separation audit ---------------------------------
    dp, stages = 2, 2
    mesh3 = make_3d_mesh(dp, stages, tp)
    tpc3 = TPCollectives(mesh3, "tensor", codec="q8", k_frac=k_frac)

    def stage3_fn(p, h):
        full = tpc3.gather(h)[0]
        part = (jax.nn.gelu((full @ p["w1"]).astype(jnp.float32))
                .astype(jnp.bfloat16) @ p["w2"])
        return h + tpc3.scatter(part)

    reduce_fn = make_grad_all_reduce(
        mesh3, "data", "q8", k_frac=k_frac,
        tp_axis="tensor", tp_dims={"w1": 3, "w2": 2})

    def step(params, dp_state, x):
        pdp = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (dp, *a.shape)), params)

        def loss(p):
            # tp_param_dims index the FULL (dp, stage, ...) leaves
            y = pipeline_apply(stage3_fn, p, x, mesh3, "stage",
                               scheme="q8", k_frac=k_frac, dp_axis="data",
                               tp_axis="tensor",
                               tp_param_dims={"w1": 3, "w2": 2}, seq_dim=1)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        g = jax.grad(loss)(pdp)
        return reduce_fn(g, dp_state)

    params3 = {
        "w1": jax.ShapeDtypeStruct((stages, d_model, d_ff), jnp.bfloat16),
        "w2": jax.ShapeDtypeStruct((stages, d_ff, d_model), jnp.bfloat16),
    }
    st = init_dp_state(params3, dp, "none")
    x3 = jax.ShapeDtypeStruct((8, 32, d_model), jnp.bfloat16)
    hlo = jax.jit(step).lower(
        params3, jax.eval_shape(lambda: st), x3).compile().as_text()
    rings = {ax: ring_pairs(mesh3, ax)
             for ax in ("data", "stage", "tensor")}
    by_ring = {ax: 0 for ax in rings}
    layout, unclassified = 0, 0
    for key, n in collective_counts(hlo, by_pairs=True).items():
        op, _, pairs_s = key.partition("|")
        if op != "collective-permute" or not pairs_s.startswith("{"):
            continue
        pairs = {tuple(int(v) for v in p.split(","))
                 for p in pairs_s[2:-2].split("},{")}
        for ax, ring in rings.items():
            if pairs <= ring:
                by_ring[ax] += n
                break
        else:
            if any(s == t for s, t in pairs):
                # a device-order remap GSPMD inserts to reshard between
                # program regions (self-pairs: rings never self-send)
                layout += n
            else:
                unclassified += n
    audit = {
        "tp_codec": "q8", "section": "3d_train_step_audit",
        "dp": dp, "stages": stages, "tp": tp,
        "data_ring_collective_permute_launches": by_ring["data"],
        "stage_ring_collective_permute_launches": by_ring["stage"],
        "tensor_ring_collective_permute_launches": by_ring["tensor"],
        "layout_collective_permute_launches": layout,
        "unclassified_collective_permute_launches": unclassified,
    }
    if check:
        # the fused DP reduce is exactly dp-1 data hops; the stage scan
        # and the per-stage TP gathers/scatters keep their own rings; no
        # WIRE launch straddles two rings (layout remaps aside)
        assert by_ring["data"] == dp - 1, audit
        assert by_ring["stage"] >= 1, audit
        assert by_ring["tensor"] >= 2, audit
        assert unclassified == 0, audit
    reports.append(audit)
    return reports


def measure_telemetry(schemes=("none", "q8", "q4", "topk", "topk_reuse"),
                      *, stages=4, batch=8, seq=256, d_model=256,
                      k_frac=0.10, check: bool = True):
    """§Telemetry: the tracer's per-boundary "pipeline.wire" payload bytes
    agree EXACTLY with this benchmark's own cost-model numbers
    (:func:`payload_bytes` — two independent derivations of the same
    eval_shape facts), per scheme."""
    from repro.transport.pipeline import (PipelineTransport,
                                          _policy_for_scheme, wire_telemetry)
    from repro.transport.schedules import as_schedule
    mb_feat = (batch // stages, seq, d_model)
    sched = as_schedule("gpipe", None)
    reports = []
    for scheme in schemes:
        fw, bw, _, _ = payload_bytes(scheme, mb_feat, k_frac)
        policy = _policy_for_scheme(scheme, k_frac)
        transport = PipelineTransport(policy, "stage", stages,
                                      fused=sched.fused_wire)
        tel = wire_telemetry(transport, sched, mb_feat, jnp.bfloat16,
                             microbatches=stages)
        agree = (tel["fw_payload_bytes_per_hop"] == fw
                 and tel["bw_payload_bytes_per_hop"] == bw)
        if check:
            assert agree, (scheme, tel, fw, bw)
        reports.append({
            "scheme": scheme, "telemetry_fw_bytes":
                tel["fw_payload_bytes_per_hop"],
            "telemetry_bw_bytes": tel["bw_payload_bytes_per_hop"],
            "cost_model_fw_bytes": fw, "cost_model_bw_bytes": bw,
            "agree_exactly": agree,
        })
    return reports


def measure_policy_audit(*, stages=4, batch=8, k_frac=0.10,
                         spec="q4@size>=65536;q8@size>=16384;none",
                         check: bool = True):
    """Per-boundary audit of an adaptive rule policy (core/policy.py).

    Resolves the spec against a HETEROGENEOUS stack — per-example cut
    sizes shrink with depth, like a pooling CNN — so a single size rule
    legitimately picks different codecs at different cuts.  One row per
    boundary: which rule fired, the resolved fw/bw compressors, and the
    exact packed payload bytes that codec puts on the wire there.
    """
    from repro.core.policy import parse_policy_rules
    from repro.transport.codecs import codec_for, wire_bytes
    feats = [(256, 512), (128, 256), (32, 128)]   # per-example (seq, d)
    sizes = [s * d for s, d in feats]
    rules = parse_policy_rules(spec, num_stages=stages)
    policy = rules.resolve(sizes)
    rows = []
    for i, (feat, size) in enumerate(zip(feats, sizes)):
        bp = policy.at(i)
        x = jax.ShapeDtypeStruct((batch // stages, *feat), jnp.bfloat16)
        fw = wire_bytes(jax.eval_shape(
            lambda a, c=bp.fw: codec_for(c).pack(a, c.k_frac), x))
        bw = wire_bytes(jax.eval_shape(
            lambda a, c=bp.bw: codec_for(c).pack(a, c.k_frac), x))
        rows.append({
            "boundary": i, "size_per_example": size,
            "fw_rule": rules.pick(size, i, "fw").name,
            "bw_rule": rules.pick(size, i, "bw").name,
            "fw_codec": bp.fw.name, "bw_codec": bp.bw.name,
            "fw_payload_bytes": fw, "bw_payload_bytes": bw,
        })
    if check:
        # the point of the rule engine: one spec, distinct per-cut codecs
        assert len({r["fw_codec"] for r in rows}) >= 2, rows
        # and shallower (bigger) cuts never pack FEWER bytes/elem than
        # deeper ones under a monotone size spec
        bpe = [r["fw_payload_bytes"] / r["size_per_example"] for r in rows]
        assert all(a <= b + 1e-6 for a, b in zip(bpe, bpe[1:])), rows
    return rows


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="regression gate: recompute and compare against "
                         "the committed results/pipeline_wire.json (wire "
                         "bytes and launch counts exact); exit 1 on drift")
    args = ap.parse_args(argv)
    reports = measure()
    for r in reports:
        print(json.dumps(r))
    fb_reports = measure_feedback()
    for r in fb_reports:
        print(json.dumps(r))
    sched_reports = measure_schedules()
    for r in sched_reports:
        print(json.dumps(r))
    dp_reports = measure_dp()
    for r in dp_reports:
        print(json.dumps(r))
    tp_reports = measure_tp()
    for r in tp_reports:
        print(json.dumps(r))
    audit_reports = measure_policy_audit()
    for r in audit_reports:
        print(json.dumps(r))
    tel_reports = measure_telemetry()
    for r in tel_reports:
        print(json.dumps(r))
    fresh = {"schemes": reports, "feedback": fb_reports,
             "schedules": sched_reports, "dp": dp_reports,
             "tp": tp_reports, "policy_audit": audit_reports,
             "telemetry": tel_reports}
    if args.check:
        from benchmarks.common import run_check
        # payload bytes and launch counts are jax-version-stable (payloads
        # come from eval_shape of OUR packing; launch counts are the fused
        # claim being gated).  Whole-program HLO collective BYTES also sum
        # XLA's internal fusion choices, so they get a band instead of
        # exact equality — a compiler upgrade shouldn't red the CI lane.
        return run_check(
            fresh, "pipeline_wire",
            band_keys={"hlo_fw_collective_permute_bytes": 0.25,
                       "hlo_fwbw_collective_permute_bytes": 0.25})
    os.makedirs(os.path.join(os.path.dirname(__file__), "results"),
                exist_ok=True)
    with open(os.path.join(os.path.dirname(__file__), "results",
                           "pipeline_wire.json"), "w") as f:
        json.dump(fresh, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
