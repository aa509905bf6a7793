"""Transport-layer tests: codec registry round-trips, wire-cost models,
simulated/real equivalence, and the differentiable pipeline (subprocess,
2 host devices — the main pytest process keeps seeing exactly one device).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import hypothesis_or_stubs
given, settings, st = hypothesis_or_stubs()

from repro.core.compressors import (quant, quantize_dequantize, topk,
                                    topk_compress)
from repro.launch.mesh import make_mesh
from repro.transport.codecs import (codec_for, get_codec, pack_payload,
                                    registered_codecs, unpack_payload,
                                    wire_bytes)

K_FRACS = (0.05, 0.1, 0.3)
DTYPES = (jnp.bfloat16, jnp.float32)
DIMS = (33, 64)          # odd and even feature dims


def _x(shape, dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


class TestCodecRoundtrip:
    @pytest.mark.parametrize("scheme", registered_codecs())
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("n", DIMS)
    @pytest.mark.parametrize("k", K_FRACS)
    def test_roundtrip_shape_finite(self, scheme, dtype, n, k):
        x = _x((3, n), dtype)
        p = pack_payload(x, scheme, k)
        y = unpack_payload(p, x.shape, dtype)
        assert y.shape == x.shape and y.dtype == dtype
        assert np.isfinite(np.asarray(y, np.float32)).all()

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_rank3_roundtrip(self, dtype):
        x = _x((2, 5, 7), dtype)       # odd flattened dim (35)
        for scheme in registered_codecs():
            y = unpack_payload(pack_payload(x, scheme, 0.3), x.shape, dtype)
            assert y.shape == x.shape

    def test_q8_matches_dense_compressor_exactly(self):
        x = _x((4, 64), jnp.float32)
        got = get_codec("q8").roundtrip(x)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(quantize_dequantize(x, 8)))

    @pytest.mark.parametrize("n", (33, 34, 64))
    def test_q4_odd_even_matches_dense_compressor(self, n):
        """The odd-feature-dim mis-pack fix: pad to even, truncate back."""
        x = _x((3, n), jnp.float32)
        got = get_codec("q4").roundtrip(x)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(quantize_dequantize(x, 4)))

    def test_topk_matches_dense_compressor(self):
        x = _x((3, 64), jnp.float32)
        got = get_codec("topk").roundtrip(x, 0.25)
        dense = topk_compress(x, 0.25)
        # wire values ride as bf16
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   rtol=1e-2, atol=1e-2)
        assert (np.asarray(got != 0) == np.asarray(dense != 0)).all()

    @given(st.sampled_from(sorted(registered_codecs())),
           st.integers(1, 4), st.integers(3, 99))
    @settings(max_examples=25, deadline=None)
    def test_roundtrip_property(self, scheme, b, n):
        x = _x((b, n), jnp.float32, seed=b * 101 + n)
        y = unpack_payload(pack_payload(x, scheme, 0.1), x.shape,
                           jnp.float32)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()


class TestTopKIndices:
    def test_uint16_when_fits(self):
        x = _x((2, 1000), jnp.float32)
        assert pack_payload(x, "topk", 0.1)["idx"].dtype == jnp.uint16

    def test_int32_when_large(self):
        x = _x((1, (1 << 16) + 8), jnp.float32)
        p = pack_payload(x, "topk", 0.01)
        assert p["idx"].dtype == jnp.int32
        y = unpack_payload(p, x.shape, jnp.float32)
        assert y.shape == x.shape

    def test_cost_model_tracks_idx_dtype(self):
        c = topk(0.1)
        assert c.wire_bytes_per_elem(2, n=1024) == pytest.approx(0.4)
        assert c.wire_bytes_per_elem(2, n=(1 << 16) + 1) == pytest.approx(0.6)
        assert c.wire_bytes_per_elem(2) == pytest.approx(0.6)  # unknown n

    def test_payload_bytes_match_cost_model(self):
        b, n, k = 4, 1024, 0.1
        x = _x((b, n), jnp.float32)
        got = wire_bytes(pack_payload(x, "topk", k))
        model = b * n * topk(k).wire_bytes_per_elem(2, n=n)
        # continuous model vs discrete k=round(k_frac*n): one elem/row slack
        assert abs(got - model) <= b * (2 + 2)


class TestCodecRegistry:
    def test_codec_for_mapping(self):
        assert codec_for(quant(8)).name == "q8"
        assert codec_for(quant(4)).name == "q4"
        assert codec_for(topk(0.1)).name == "topk"
        with pytest.raises(ValueError):
            codec_for(quant(6))

    def test_unknown_scheme_raises(self):
        with pytest.raises(ValueError):
            pack_payload(jnp.zeros((1, 4)), "zstd")

    def test_quant_payload_bytes_match_cost_model(self):
        b, n = 4, 256
        x = _x((b, n), jnp.float32)
        for bits in (4, 8):
            got = wire_bytes(pack_payload(x, f"q{bits}"))
            model = b * n * quant(bits).wire_bytes_per_elem(2)
            assert abs(got - model) <= 16   # per-tensor min/scale scalars


# ---------------------------------------------------------------------------
# Differentiable pipeline (subprocess: 2 host devices)
# ---------------------------------------------------------------------------

GRAD_EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp
    from repro.transport.pipeline import pipeline_apply
    S, B, D = 2, 4, 16
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, D), jnp.float32)
    k1, k2 = jax.random.split(key)
    params = {"w1": jax.random.normal(k1, (S, D, 2 * D)) * 0.1,
              "w2": jax.random.normal(k2, (S, 2 * D, D)) * 0.1}
    stage_fn = lambda p, h: h + jnp.tanh(h @ p["w1"]) @ p["w2"]

    def seq_loss(params, x):
        h = x
        for s in range(S):
            h = stage_fn(jax.tree.map(lambda a: a[s], params), h)
            if s < S - 1:   # wire casts to bf16; cotangent rounds through too
                h = h.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.sum(h ** 2)

    def pipe_loss(params, x):
        out = pipeline_apply(stage_fn, params, x, mesh, "stage",
                             scheme="none")
        return jnp.sum(out ** 2)

    ls, gs = jax.value_and_grad(seq_loss)(params, x)
    lp, gp = jax.value_and_grad(pipe_loss)(params, x)
    assert abs(float(ls - lp)) < 1e-4, (float(ls), float(lp))
    for k in gs:
        d = float(jnp.max(jnp.abs(gs[k] - gp[k])))
        m = float(jnp.max(jnp.abs(gs[k]))) + 1e-9
        assert d / m < 1e-5, (k, d, m)
    gxs = jax.grad(seq_loss, argnums=1)(params, x)
    gxp = jax.grad(pipe_loss, argnums=1)(params, x)
    assert float(jnp.max(jnp.abs(gxs - gxp))) < 1e-5
    print("GRAD_EQUIV_OK")
""")


TRAIN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp
    from repro.core.boundary import boundary_apply
    from repro.core.feedback import FeedbackState
    from repro.core.policy import CompressionPolicy, quant_policy, topk_policy
    from repro.data.synthetic import ImageClassData
    from repro.models import cnn
    from repro.optim.optimizers import (OptimizerConfig, apply_updates,
                                        init_opt_state)
    from repro.train.steps import make_cnn_train_step, xent_loss

    data = ImageClassData()
    opt = OptimizerConfig(kind="sgd", lr=0.05, momentum=0.9,
                          schedule="constant")
    params0 = cnn.init_pipeline_params(jax.random.PRNGKey(0), 2, width=8)

    def run(pol, steps=10):
        step = make_cnn_train_step(pol, opt, transport="pipeline")
        p, o = params0, init_opt_state(opt, params0)
        losses = []
        for i, (x, y, ids) in enumerate(data.epoch(50, 0)):
            if i >= steps:
                break
            p, o, _, m = step(p, o, [], jnp.asarray(x), jnp.asarray(y),
                              jnp.asarray(ids))
            losses.append(float(m["loss"]))
        return losses

    # q8: the real pipeline must track the simulated boundary step-for-step
    pol = CompressionPolicy(num_stages=2, boundary=quant_policy(8, 8))
    pipe = run(pol)

    def seq_loss(params, images, labels):
        x = cnn.pipeline_stem(params, images)
        n = params["stages"]["b0"]["conv1"].shape[0]
        for s in range(n):
            x = cnn.pipeline_stage_apply(
                jax.tree.map(lambda a: a[s], params["stages"]), x)
            if s < n - 1:
                z = jnp.zeros((0,))
                x, _ = boundary_apply(
                    pol.at(s), x,
                    FeedbackState(resid=z, mirror=z, agg=z, direction="fw"),
                    FeedbackState(resid=z, mirror=z, agg=z, direction="bw"),
                    jnp.zeros((x.shape[0],), jnp.int32))
        return xent_loss(cnn.pipeline_head(params, x), labels)

    @jax.jit
    def sstep(p, o, x, y):
        loss, g = jax.value_and_grad(seq_loss)(p, x, y)
        p, o = apply_updates(opt, p, g, o)
        return p, o, loss

    p, o = params0, init_opt_state(opt, params0)
    seq = []
    for i, (x, y, ids) in enumerate(data.epoch(50, 0)):
        if i >= len(pipe):
            break
        p, o, l = sstep(p, o, jnp.asarray(x), jnp.asarray(y))
        seq.append(float(l))
    for a, b in zip(pipe, seq):
        assert abs(a - b) < 0.02 * max(abs(b), 1.0), (pipe, seq)
    assert pipe[-1] < pipe[0], pipe

    # topk: training loss decreases through the sparse wire
    pipe_t = run(CompressionPolicy(num_stages=2,
                                   boundary=topk_policy(0.10)))
    assert pipe_t[-1] < pipe_t[0], pipe_t
    print("TRAIN_OK", pipe[-1], pipe_t[-1])
""")


# ---------------------------------------------------------------------------
# Error feedback over the real wire (subprocess: 2 host devices)
# ---------------------------------------------------------------------------

FEEDBACK_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.boundary import boundary_apply
    from repro.core.feedback import FeedbackState
    from repro.core.policy import BoundaryPolicy, aqsgd_policy, ef_policy
    from repro.core.compressors import quant
    from repro.transport.pipeline import pipeline_apply, init_feedback_state

    def fbs(arr, mode, direction):
        z = jnp.zeros((0,))
        return FeedbackState(resid=arr, mirror=z, agg=z, mode=mode,
                             direction=direction)

    S, B, D, MB = 2, 4, 16, 2
    MBSZ = B // MB
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    params0 = {"w1": jax.random.normal(k1, (S, D, 2 * D)) * 0.1,
               "w2": jax.random.normal(k2, (S, 2 * D, D)) * 0.1}
    stage_fn = lambda p, h: h + jnp.tanh(h @ p["w1"]) @ p["w2"]
    LR = 0.05

    def pipe_train(bp, num_samples, steps, seed=0, schedule="gpipe"):
        '''SGD-train through the real wire; returns (losses, final state).'''
        st = init_feedback_state(bp, (D,), num_stages=S, batch=B,
                                 num_samples=num_samples)
        params = params0

        @jax.jit
        def train_step(params, fw_state, bw_state, x, ids):
            def loss_fn(params, bw_state):
                y, new_fw = pipeline_apply(
                    stage_fn, params, x, mesh, "stage", policy=bp,
                    schedule=schedule,
                    fw_state=fw_state, bw_state=bw_state, ids=ids)
                return jnp.sum(y.astype(jnp.float32) ** 2) / B, new_fw
            (l, new_fw), (g, new_bw) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params, bw_state)
            params = jax.tree.map(lambda p, gg: p - LR * gg, params, g)
            return params, new_fw, new_bw, l

        rng = np.random.RandomState(seed)
        losses = []
        for t in range(steps):
            x = jnp.asarray(rng.randn(B, D), jnp.float32)
            n = max(num_samples, B)
            ids = jnp.asarray(rng.permutation(n)[:B], jnp.int32)
            params, fw, bw, l = train_step(params, st["fw"], st["bw"],
                                           x, ids)
            st = {"fw": fw, "bw": bw}
            losses.append(float(l))
        return losses, st, params

    def sim_train(bp, num_samples, steps, seed=0):
        '''Reference: simulated boundary applied per microbatch (the GPipe
        schedule the pipeline runs), same SGD.'''
        if bp.feedback == "aqsgd":
            fw = jnp.zeros((num_samples, D))
        elif bp.feedback != "none":
            fw = jnp.zeros((B, D))
        else:
            fw = jnp.zeros((0,))
        bw = jnp.zeros((B, D)) if bp.bw_feedback != "none" else jnp.zeros((0,))
        params = params0

        @jax.jit
        def train_step(params, fw_buf, bw_buf, x, ids):
            def loss_fn(params, bw_buf):
                ys, nfs = [], []
                fwb = fw_buf
                for j in range(MB):
                    sl = slice(j * MBSZ, (j + 1) * MBSZ)
                    fb = (fwb if bp.feedback == "aqsgd" else
                          (fwb[sl] if bp.feedback != "none"
                           else jnp.zeros((0,))))
                    bb = (bw_buf[sl] if bp.bw_feedback != "none"
                          else jnp.zeros((0,)))
                    h = stage_fn(jax.tree.map(lambda a: a[0], params), x[sl])
                    h, nf = boundary_apply(bp, h, fbs(fb, bp.feedback, "fw"),
                                           fbs(bb, bp.bw_feedback, "bw"),
                                           ids[sl])
                    nf = nf.resid
                    if bp.feedback == "aqsgd":
                        fwb = nf
                    h = stage_fn(jax.tree.map(lambda a: a[1], params), h)
                    ys.append(h)
                    nfs.append(nf)
                y = jnp.concatenate(ys, 0)
                nf = (fwb if bp.feedback == "aqsgd" else
                      (jnp.concatenate(nfs, 0) if bp.feedback != "none"
                       else fw_buf))
                return jnp.sum(y.astype(jnp.float32) ** 2) / B, nf
            (l, new_fw), (g, new_bw) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params, bw_buf)
            params = jax.tree.map(lambda p, gg: p - LR * gg, params, g)
            return params, new_fw, new_bw, l

        rng = np.random.RandomState(seed)
        losses = []
        for t in range(steps):
            x = jnp.asarray(rng.randn(B, D), jnp.float32)
            n = max(num_samples, B)
            ids = jnp.asarray(rng.permutation(n)[:B], jnp.int32)
            params, fw, bw, l = train_step(params, fw, bw, x, ids)
            losses.append(float(l))
        return losses, (fw, bw), params
""")


FEEDBACK_EQUIV_SCRIPT = FEEDBACK_COMMON + textwrap.dedent("""
    # (a) EF / AQ-SGD training through the real wire tracks the simulated
    # boundary STEP-FOR-STEP (q8: the wire roundtrip is bit-identical to
    # the dense compressor, so the bar is float accumulation error)
    q8 = quant(8)
    for bp, ns, tag in [
        (BoundaryPolicy(fw=q8, bw=q8, feedback="ef", bw_feedback="ef"),
         0, "ef"),
        (BoundaryPolicy(fw=q8, bw=q8, feedback="ef21", bw_feedback="ef21"),
         0, "ef21"),
        (BoundaryPolicy(fw=q8, bw=q8, feedback="aqsgd"), 12, "aqsgd"),
    ]:
        pl, pst, pp = pipe_train(bp, ns, steps=6)
        slr, (sfw, sbw), sp = sim_train(bp, ns, steps=6)
        for t, (a, b) in enumerate(zip(pl, slr)):
            assert abs(a - b) < 1e-4 * max(abs(b), 1.0), (tag, t, pl, slr)
        dp = max(float(jnp.max(jnp.abs(pp[k] - sp[k]))) for k in pp)
        assert dp < 1e-4, (tag, dp)
        # pipeline cut-0 buffer == simulated buffer (stage 0 owns cut 0)
        if bp.feedback == "aqsgd":
            d = float(jnp.max(jnp.abs(pst["fw"].resid[0] - sfw)))
            dm = float(jnp.max(jnp.abs(pst["fw"].mirror[1] - sfw)))
            assert d < 1e-4 and dm < 1e-4, (tag, d, dm)
        else:
            d = float(jnp.max(jnp.abs(
                pst["fw"].resid[0].reshape(B, D) - sfw)))
            assert d < 1e-4, (tag, d)
        print(tag, "tracks simulated:", pl[-1], slr[-1])

    # (b) AQ-SGD buffers update ONLY the example ids actually seen
    bp = BoundaryPolicy(fw=q8, bw=q8, feedback="aqsgd")
    st = init_feedback_state(bp, (D,), num_stages=S, batch=B, num_samples=16)
    seen = jnp.asarray([3, 7, 11, 1], jnp.int32)
    def loss_fn(params, bw_state, fw_state, x):
        y, new_fw = pipeline_apply(stage_fn, params, x, mesh, "stage",
                                   policy=bp, fw_state=fw_state,
                                   bw_state=bw_state, ids=seen)
        return jnp.sum(y ** 2), new_fw
    x = jax.random.normal(jax.random.PRNGKey(5), (B, D))
    (_, nf), _ = jax.value_and_grad(loss_fn, has_aux=True)(
        params0, st["bw"], st["fw"], x)
    touched = np.nonzero(np.asarray(
        jnp.any(nf.resid[0].reshape(16, -1) != 0, axis=-1)))[0]
    assert set(touched) <= set(np.asarray(seen).tolist()), touched
    assert len(touched) == B, touched

    # (c) feedback='none': size-0 buffers ride the scan carry untouched
    none_bp = BoundaryPolicy(fw=q8, bw=q8)
    st0 = init_feedback_state(none_bp, (D,), num_stages=S, batch=B)
    assert all(st0[d].resid.shape == (S, 0)
               and st0[d].mirror.shape == (S, 0) for d in ("fw", "bw")), st0
    y, nf0 = pipeline_apply(stage_fn, params0, x, mesh, "stage",
                            policy=none_bp, fw_state=st0["fw"],
                            bw_state=st0["bw"])
    assert nf0.resid.shape == (S, 0) and nf0.mirror.shape == (S, 0), nf0
    print("FEEDBACK_EQUIV_OK")
""")


FEEDBACK_TOPK_SCRIPT = FEEDBACK_COMMON + textwrap.dedent("""
    # AQ-SGD + TopK (paper Table 4 config) over the real wire: training
    # tracks the simulated boundary step-for-step.  TopK wire values ride
    # as bf16 while the dense compressor keeps fp32, so the bar is a loss
    # tolerance over a short horizon (selection is discontinuous: a tie
    # flip separates otherwise-equivalent trajectories).
    for bp, ns, tag in [(aqsgd_policy(0.3), 12, "aqsgd+top30"),
                        (ef_policy(0.3, "ef"), 0, "ef+top30")]:
        pl, _, _ = pipe_train(bp, ns, steps=5)
        sl, _, _ = sim_train(bp, ns, steps=5)
        for t, (a, b) in enumerate(zip(pl, sl)):
            assert abs(a - b) < 0.03 * max(abs(b), 1.0), (tag, t, pl, sl)
        print(tag, "tracks simulated:", pl[-1], sl[-1])

    # and compensated TopK training makes progress through the real wire
    pl, _, _ = pipe_train(aqsgd_policy(0.3), 12, steps=10)
    assert pl[-1] < pl[0], pl
    print("FEEDBACK_TOPK_OK")
""")


FEEDBACK_DP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.policy import BoundaryPolicy
    from repro.core.compressors import quant
    from repro.launch.mesh import make_dp_pipeline_mesh
    from repro.transport.pipeline import pipeline_apply, init_feedback_state
    from repro.transport.collectives import (init_dp_state,
                                             make_grad_all_reduce)

    DP, S, B, D, MB = 2, 2, 8, 16, 2
    SH = B // DP                          # per-replica shard
    mesh = make_dp_pipeline_mesh(DP, S)
    from repro.launch.mesh import make_mesh
    mesh1 = make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    params0 = {"w1": jax.random.normal(k1, (S, D, 2 * D)) * 0.1,
               "w2": jax.random.normal(k2, (S, 2 * D, D)) * 0.1}
    stage_fn = lambda p, h: h + jnp.tanh(h @ p["w1"]) @ p["w2"]
    LR = 0.05
    q8 = quant(8)

    def dp_train(bp, steps, num_samples=0, ids_fn=None):
        '''2x2 mesh: boundary feedback states carry a leading (dp,) dim;
        gradients reduce EXACTLY (codec none), so any trajectory drift vs
        the per-shard solo reference is the boundary feedback itself.'''
        st = init_feedback_state(bp, (D,), num_stages=S, batch=B,
                                 microbatches=MB, num_samples=num_samples,
                                 dp=DP)
        reduce_fn = make_grad_all_reduce(mesh, "data", "none")
        dpst = init_dp_state(params0, DP, "none")

        @jax.jit
        def train_step(params, fw_state, bw_state, dpst, x, ids):
            pdp = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (DP, *a.shape)), params)
            def loss_fn(pdp, bw_state):
                y, new_fw = pipeline_apply(
                    stage_fn, pdp, x, mesh, "stage", policy=bp,
                    microbatches=MB, dp_axis="data",
                    fw_state=fw_state, bw_state=bw_state, ids=ids)
                return jnp.sum(y.astype(jnp.float32) ** 2) / B, new_fw
            (l, new_fw), (g_dp, new_bw) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(pdp, bw_state)
            g, dpst = reduce_fn(g_dp, dpst)
            params = jax.tree.map(lambda p, gg: p - LR * gg, params, g)
            return params, new_fw, new_bw, dpst, l

        rng = np.random.RandomState(0)
        params, losses = params0, []
        for t in range(steps):
            x = jnp.asarray(rng.randn(B, D), jnp.float32)
            ids = (ids_fn(rng) if ids_fn is not None
                   else jnp.zeros((B,), jnp.int32))
            params, fw, bw, dpst, l = train_step(
                params, st["fw"], st["bw"], dpst, x, ids)
            st = {"fw": fw, "bw": bw}
            losses.append(float(l))
        return losses, st, params

    def solo_train(bp, steps, num_samples=0, ids_fn=None):
        '''Reference: each replica's shard through the SAME single-replica
        pipeline program with its own feedback state; shard grads summed
        serially (what an exact DP reduce computes).'''
        ns_sh = num_samples // DP if num_samples else 0
        sts = [init_feedback_state(bp, (D,), num_stages=S, batch=SH,
                                   microbatches=MB, num_samples=ns_sh)
               for _ in range(DP)]

        @jax.jit
        def shard_grad(params, fw_state, bw_state, xs, ids):
            def loss_fn(params, bw_state):
                y, new_fw = pipeline_apply(
                    stage_fn, params, xs, mesh1, "stage", policy=bp,
                    microbatches=MB, fw_state=fw_state,
                    bw_state=bw_state, ids=ids)
                return jnp.sum(y.astype(jnp.float32) ** 2) / B, new_fw
            (l, new_fw), (g, new_bw) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params, bw_state)
            return l, g, new_fw, new_bw

        rng = np.random.RandomState(0)
        params, losses = params0, []
        for t in range(steps):
            x = jnp.asarray(rng.randn(B, D), jnp.float32)
            ids = (ids_fn(rng) if ids_fn is not None
                   else jnp.zeros((B,), jnp.int32))
            ltot, g = 0.0, None
            for r in range(DP):
                sl = slice(r * SH, (r + 1) * SH)
                lids = ids[sl] - r * ns_sh     # replica-local buffer rows
                l, gr, nf, nb = shard_grad(params, sts[r]["fw"],
                                           sts[r]["bw"], x[sl], lids)
                sts[r] = {"fw": nf, "bw": nb}
                ltot = ltot + l
                g = gr if g is None else jax.tree.map(jnp.add, g, gr)
            params = jax.tree.map(lambda p, gg: p - LR * gg, params, g)
            losses.append(float(ltot))
        return losses, sts, params

    # (a) EF / EF21 boundary feedback + dp: the 2x2 run tracks the
    # per-shard solo reference step-for-step, and replica r's slice of
    # the sharded feedback state equals solo run r's state
    for mode in ("ef", "ef21"):
        bp = BoundaryPolicy(fw=q8, bw=q8, feedback=mode, bw_feedback=mode)
        dl, dst, dparams = dp_train(bp, 6)
        slr, ssts, sparams = solo_train(bp, 6)
        for t, (a, b) in enumerate(zip(dl, slr)):
            assert abs(a - b) < 1e-4 * max(abs(b), 1.0), (mode, t, dl, slr)
        dmax = max(float(np.max(np.abs(
            np.asarray(dparams[k]) - np.asarray(sparams[k]))))
            for k in dparams)
        assert dmax < 1e-4, (mode, dmax)
        for r in range(DP):
            for dname in ("fw", "bw"):
                d = float(np.max(np.abs(
                    np.asarray(dst[dname].resid)[r]
                    - np.asarray(ssts[r][dname].resid))))
                assert d < 1e-4, (mode, dname, r, d)
        print(mode, "+dp tracks per-shard solo:", dl[-1], slr[-1])

    # (b) AQ-SGD + dp: id-sharded buffers — with the routing contract
    # (example i lives on replica i // (NS/DP)) training matches the
    # per-shard solo reference and each replica touches ONLY its rows
    NS = 16
    PER = NS // DP
    bp = BoundaryPolicy(fw=q8, bw=q8, feedback="aqsgd")

    def routed_ids(rng):
        return jnp.asarray(np.concatenate(
            [rng.permutation(PER)[:SH] + r * PER for r in range(DP)]),
            jnp.int32)

    dl, dst, dparams = dp_train(bp, 5, num_samples=NS, ids_fn=routed_ids)
    slr, ssts, sparams = solo_train(bp, 5, num_samples=NS,
                                    ids_fn=routed_ids)
    for t, (a, b) in enumerate(zip(dl, slr)):
        assert abs(a - b) < 1e-4 * max(abs(b), 1.0), (t, dl, slr)
    for r in range(DP):
        d = float(np.max(np.abs(np.asarray(dst["fw"].resid)[r]
                                - np.asarray(ssts[r]["fw"].resid))))
        assert d < 1e-4, (r, d)
    print("aqsgd+dp tracks per-shard solo:", dl[-1], slr[-1])

    # single known step: the touched buffer rows are EXACTLY the local
    # ids each replica saw (gather/scatter stayed replica-local)
    st = init_feedback_state(bp, (D,), num_stages=S, batch=B,
                             microbatches=MB, num_samples=NS, dp=DP)
    ids = jnp.asarray([3, 7, 1, 5, 10, 14, 8, 12], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(9), (B, D))
    pdp = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (DP, *a.shape)), params0)
    def one(pdp, bw_state):
        y, new_fw = pipeline_apply(stage_fn, pdp, x, mesh, "stage",
                                   policy=bp, microbatches=MB,
                                   dp_axis="data", fw_state=st["fw"],
                                   bw_state=bw_state, ids=ids)
        return jnp.sum(y.astype(jnp.float32) ** 2), new_fw
    (_, nf), _ = jax.value_and_grad(one, has_aux=True)(pdp, st["bw"])
    for r, local in ((0, {3, 7, 1, 5}), (1, {2, 6, 0, 4})):
        rows = np.asarray(jnp.any(
            nf.resid[r][0].reshape(PER, D) != 0, axis=-1))
        touched = set(np.nonzero(rows)[0].tolist())
        assert touched == local, (r, touched, local)
    print("FEEDBACK_DP_OK")
""")


# ---------------------------------------------------------------------------
# Pipeline schedules (transport/schedules.py)
# ---------------------------------------------------------------------------

SCHEDULE_EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.transport.pipeline import pipeline_apply
    S, B, D, MB = 2, 8, 16, 8
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    params = {"w1": jax.random.normal(k1, (S, D, 2 * D)) * 0.1,
              "w2": jax.random.normal(k2, (S, 2 * D, D)) * 0.1}
    stage_fn = lambda p, h: h + jnp.tanh(h @ p["w1"]) @ p["w2"]
    x = jax.random.normal(key, (B, D), jnp.float32)

    def loss(sched, scheme):
        def f(p, xx):
            out = pipeline_apply(stage_fn, p, xx, mesh, "stage",
                                 scheme=scheme, microbatches=MB,
                                 schedule=sched)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.value_and_grad(f)(params, x)

    # 1F1B (rematerialized ticks + fused single-buffer hops) is the SAME
    # math as GPipe — bit-for-bit, loss AND grads, with microbatches >>
    # stages, compressed or not
    for scheme in ("none", "q8"):
        lg, gg = loss("gpipe", scheme)
        lf, gf = loss("1f1b", scheme)
        assert float(lg) == float(lf), (scheme, float(lg), float(lf))
        for k in gg:
            assert np.array_equal(np.asarray(gg[k]), np.asarray(gf[k])), \\
                (scheme, k)
        print("1f1b == gpipe bitwise:", scheme, float(lg))

    # interleaved validation: microbatch count must tile the stage count
    try:
        pipeline_apply(stage_fn, params, x, mesh, "stage", scheme="none",
                       microbatches=3, schedule="interleaved",
                       virtual_stages=2)
        raise SystemExit("interleaved mb % S accepted")
    except ValueError as e:
        assert "divisible" in str(e), e
    print("SCHEDULE_EQUIV_OK")
""")


SCHEDULE_INTERLEAVED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.boundary import boundary_apply
    from repro.core.compressors import quant
    from repro.core.feedback import FeedbackState
    from repro.core.policy import BoundaryPolicy, quant_policy
    from repro.transport.pipeline import (init_feedback_state,
                                          pipeline_apply)

    def fbs(arr, mode, direction):
        z = jnp.zeros((0,))
        return FeedbackState(resid=arr, mirror=z, agg=z, mode=mode,
                             direction=direction)

    S, V, B, D, MB = 2, 2, 8, 16, 4
    MBSZ = B // MB
    L = S * V
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    pL = {"w1": jax.random.normal(k1, (L, D, 2 * D)) * 0.1,
          "w2": jax.random.normal(k2, (L, 2 * D, D)) * 0.1}

    # (a) scheme='none' on bf16 activations: the wire cast is the identity,
    # so interleaved(v=2) must equal GPipe whose stage_fn composes the same
    # two chunks back to back — BIT FOR BIT in the loss — and must equal
    # the per-microbatch sequential reference with the wire cast at EVERY
    # logical cut bit-for-bit in loss AND grads.  (Composing chunks inside
    # one gpipe stage removes two backward-direction bf16 casts, so grads
    # vs composed-gpipe agree only to bf16 precision — the per-cut
    # reference is the exact semantic twin.)
    def chunk_fn(p, h):
        return (h + jnp.tanh(h @ p["w1"]) @ p["w2"]).astype(h.dtype)

    def composed_fn(p, h):      # gpipe stage = v chunks, no cut between
        for q in range(V):
            h = chunk_fn(jax.tree.map(lambda a: a[q], p), h)
        return h

    x16 = jax.random.normal(key, (B, D), jnp.float32).astype(jnp.bfloat16)
    p_dev = jax.tree.map(lambda a: a.reshape(S, V, *a.shape[1:]), pL)

    def g_loss(p, xx):
        out = pipeline_apply(composed_fn, p, xx, mesh, "stage",
                             scheme="none", microbatches=MB)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def cut_seq_loss(p, xx):
        hs = []
        for j in range(MB):
            h = xx[j * MBSZ:(j + 1) * MBSZ]
            for l in range(L):
                h = chunk_fn(jax.tree.map(lambda a: a[l], p), h)
                h = h.astype(jnp.bfloat16)       # the wire, at every cut
            hs.append(h)
        return jnp.sum(jnp.concatenate(hs).astype(jnp.float32) ** 2)

    def i_loss(p, xx):
        out = pipeline_apply(chunk_fn, p, xx, mesh, "stage", scheme="none",
                             microbatches=MB, schedule="interleaved",
                             virtual_stages=V)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    lg = g_loss(p_dev, x16)
    lc, gc = jax.value_and_grad(cut_seq_loss)(pL, x16)
    li, gi = jax.value_and_grad(i_loss)(pL, x16)
    assert float(lg) == float(li) == float(lc), \\
        (float(lg), float(li), float(lc))
    for k in gc:
        assert np.array_equal(np.asarray(gc[k]), np.asarray(gi[k])), k
    print("interleaved == gpipe loss bitwise; == per-cut sequential "
          "loss+grads bitwise (none/bf16):", float(li))

    # (b) q8: interleaved crosses 3 quantized cuts; the reference is the
    # SIMULATED boundary applied per microbatch at every logical cut —
    # matches to 1e-4 (straight-through bw compression included).
    bp = quant_policy(8, 8)
    stage_fn = lambda p, h: h + jnp.tanh(h @ p["w1"]) @ p["w2"]
    x = jax.random.normal(key, (B, D), jnp.float32)

    def seq_loss(p, xx):
        hs = []
        for j in range(MB):
            h = xx[j * MBSZ:(j + 1) * MBSZ]
            for l in range(L):
                h = stage_fn(jax.tree.map(lambda a: a[l], p), h)
                if l < L - 1:
                    h, _ = boundary_apply(bp, h,
                                          fbs(jnp.zeros((0,)), "none", "fw"),
                                          fbs(jnp.zeros((0,)), "none", "bw"),
                                          jnp.zeros((MBSZ,), jnp.int32))
            hs.append(h)
        return jnp.sum(jnp.concatenate(hs).astype(jnp.float32) ** 2)

    def int_loss(p, xx):
        out = pipeline_apply(stage_fn, p, xx, mesh, "stage", scheme="q8",
                             microbatches=MB, schedule="interleaved",
                             virtual_stages=V)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    ls, gs = jax.value_and_grad(seq_loss)(pL, x)
    li, gi = jax.value_and_grad(int_loss)(pL, x)
    assert abs(float(ls - li)) < 1e-4 * max(abs(float(ls)), 1.0), \\
        (float(ls), float(li))
    for k in gs:
        d = float(jnp.max(jnp.abs(gs[k] - gi[k])))
        m = float(jnp.max(jnp.abs(gs[k]))) + 1e-9
        assert d / m < 1e-4, (k, d, m)
    print("interleaved q8 matches per-cut simulated boundary:",
          float(ls), float(li))

    # (c) feedback under interleaved: EF21+q8 both directions exercises
    # the chunk-indexed buffers — send slices, delta-coded recv MIRRORS,
    # and bw cotangent buffers all carry a (S, v, ...) chunk dim.  Cut
    # l = k*S + d maps to the fw sender's slot [l % S, l // S] and the
    # receiver-side slots [(l+1) % S, (l+1) // S].
    bp21 = BoundaryPolicy(fw=quant(8), bw=quant(8),
                          feedback="ef21", bw_feedback="ef21")
    st = init_feedback_state(bp21, (D,), num_stages=S, batch=B,
                             microbatches=MB, virtual_stages=V)
    ids0 = jnp.zeros((B,), jnp.int32)

    def pipe_fb_loss(p, bw_state):
        y, new_fw = pipeline_apply(stage_fn, p, x, mesh, "stage",
                                   policy=bp21, microbatches=MB,
                                   schedule="interleaved", virtual_stages=V,
                                   fw_state=st["fw"], bw_state=bw_state,
                                   ids=ids0)
        return jnp.sum(y.astype(jnp.float32) ** 2), new_fw
    (lp, nfp), (gp, nbp) = jax.value_and_grad(
        pipe_fb_loss, argnums=(0, 1), has_aux=True)(pL, st["bw"])

    fw0 = jnp.zeros((L - 1, B, D))

    def seq_fb_loss(p, bw_bufs):
        ys, nfs = [], []
        for j in range(MB):
            sl = slice(j * MBSZ, (j + 1) * MBSZ)
            h = x[sl]
            cut_nf = []
            for l in range(L):
                h = stage_fn(jax.tree.map(lambda a: a[l], p), h)
                if l < L - 1:
                    h, nf = boundary_apply(bp21, h,
                                           fbs(fw0[l, sl], "ef21", "fw"),
                                           fbs(bw_bufs[l, sl], "ef21", "bw"),
                                           ids0[sl])
                    cut_nf.append(nf.resid)
            ys.append(h)
            nfs.append(cut_nf)
        y = jnp.concatenate(ys, 0)
        nf_full = jnp.stack([
            jnp.concatenate([nfs[j][l] for j in range(MB)], 0)
            for l in range(L - 1)])
        return jnp.sum(y.astype(jnp.float32) ** 2), nf_full
    (lr, nfr), (gr, nbr) = jax.value_and_grad(
        seq_fb_loss, argnums=(0, 1), has_aux=True)(
            pL, jnp.zeros((L - 1, B, D)))

    assert abs(float(lp - lr)) < 1e-4 * max(abs(float(lr)), 1.0), \\
        (float(lp), float(lr))
    for k in gr:
        d = float(jnp.max(jnp.abs(gr[k] - gp[k])))
        m = float(jnp.max(jnp.abs(gr[k]))) + 1e-9
        assert d / m < 1e-4, (k, d, m)
    for l in range(L - 1):
        snd, rcv = (l % S, l // S), ((l + 1) % S, (l + 1) // S)
        for tag, got, want in [
                ("fw send", nfp.resid[snd].reshape(B, D), nfr[l]),
                ("fw mirror", nfp.mirror[rcv].reshape(B, D), nfr[l]),
                ("bw send", nbp.resid[rcv].reshape(B, D), nbr[l]),
                ("bw mirror", nbp.mirror[snd].reshape(B, D), nbr[l])]:
            d = float(jnp.max(jnp.abs(got - want)))
            assert d < 1e-4, (tag, l, d)
    print("interleaved EF21 buffers match per-cut simulated boundary")
    print("SCHEDULE_INTERLEAVED_OK")
""")


SCHEDULE_FEEDBACK_SCRIPT = FEEDBACK_COMMON + textwrap.dedent("""
    # EF / AQ-SGD buffers under 1F1B match the simulated boundary
    # step-for-step (q8 wire: exact roundtrip), exactly like the gpipe
    # acceptance test — the feedback machinery is schedule-agnostic.
    q8c = quant(8)
    for bp, ns, tag in [
        (BoundaryPolicy(fw=q8c, bw=q8c, feedback="ef", bw_feedback="ef"),
         0, "ef"),
        (BoundaryPolicy(fw=q8c, bw=q8c, feedback="aqsgd"), 12, "aqsgd"),
    ]:
        pl, pst, pp = pipe_train(bp, ns, steps=5, schedule="1f1b")
        slr, (sfw, sbw), sp = sim_train(bp, ns, steps=5)
        for t, (a, b) in enumerate(zip(pl, slr)):
            assert abs(a - b) < 1e-4 * max(abs(b), 1.0), (tag, t, pl, slr)
        dp = max(float(jnp.max(jnp.abs(pp[k] - sp[k]))) for k in pp)
        assert dp < 1e-4, (tag, dp)
        if bp.feedback == "aqsgd":
            d = float(jnp.max(jnp.abs(pst["fw"].resid[0] - sfw)))
            dm = float(jnp.max(jnp.abs(pst["fw"].mirror[1] - sfw)))
            assert d < 1e-4 and dm < 1e-4, (tag, d, dm)
        else:
            d = float(jnp.max(jnp.abs(
                pst["fw"].resid[0].reshape(B, D) - sfw)))
            assert d < 1e-4, (tag, d)
        print(tag, "under 1f1b tracks simulated:", pl[-1], slr[-1])
    print("SCHEDULE_FEEDBACK_OK")
""")


def _run_sub(script):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)


def test_pipeline_gradients_match_sequential_subprocess():
    """Satellite: 2-stage CPU gradient equivalence, scheme='none'."""
    r = _run_sub(GRAD_EQUIV_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "GRAD_EQUIV_OK" in r.stdout


@pytest.mark.slow
def test_pipeline_training_decreases_loss_subprocess():
    """Acceptance: 2-stage CNN training through the real ppermute path
    with q8 (tracks the simulated boundary step-for-step) and topk."""
    r = _run_sub(TRAIN_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "TRAIN_OK" in r.stdout


@pytest.mark.slow
def test_pipeline_feedback_matches_simulated_subprocess():
    """Acceptance (run explicitly in CI): EF/EF21/AQ-SGD training through
    the real compressed ppermute wire tracks the simulated boundary
    step-for-step (q8 — exact wire roundtrip); AQ-SGD buffers touch only
    the ids in flight; feedback='none' buffers stay size-0 in the carry."""
    r = _run_sub(FEEDBACK_EQUIV_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FEEDBACK_EQUIV_OK" in r.stdout


def test_pipeline_feedback_topk_tracks_simulated_subprocess():
    """Paper Table 4 config (AQ-SGD + TopK) over the real wire: loss
    curves track the simulated boundary and training makes progress."""
    r = _run_sub(FEEDBACK_TOPK_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FEEDBACK_TOPK_OK" in r.stdout


@pytest.mark.slow
def test_dp_pipeline_boundary_feedback_subprocess():
    """Acceptance (run explicitly in CI): boundary feedback on the 2x2
    DPxPP mesh.  EF / EF21 with dp-sharded buffers track a per-shard
    single-replica pipeline reference step-for-step (exact grad reduce
    isolates the feedback path), and AQ-SGD's id-sharded buffer touches
    only the example ids each replica saw."""
    r = _run_sub(FEEDBACK_DP_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FEEDBACK_DP_OK" in r.stdout


# ---------------------------------------------------------------------------
# Schedule subsystem
# ---------------------------------------------------------------------------

class TestSchedulePlans:
    """Pure schedule-math checks: the per-tick plan simulated with numpy —
    no devices, no shard_map."""

    @pytest.mark.parametrize("s,v,mb", [(2, 1, 2), (2, 1, 8), (4, 1, 4),
                                        (2, 2, 4), (4, 2, 8), (2, 3, 6)])
    def test_every_pair_computed_once_in_dependency_order(self, s, v, mb):
        from repro.transport.schedules import get_schedule
        sched = (get_schedule("interleaved", v) if v > 1
                 else get_schedule("gpipe"))
        sched.validate(mb, s)
        ticks = sched.num_ticks(mb, s)
        when = {}                      # (logical stage, microbatch) -> tick
        for t in range(ticks):
            for d in range(s):
                pl = sched.plan(jnp.int32(t), jnp.int32(d), mb, s)
                if not bool(pl.valid):
                    continue
                lg = int(pl.k) * s + d
                key = (lg, int(pl.j))
                assert key not in when, key
                when[key] = t
                assert bool(pl.inject) == (lg == 0)
                assert bool(pl.last) == (lg == s * v - 1)
        assert len(when) == s * v * mb
        for (lg, j), t in when.items():
            if lg > 0:     # input produced one tick earlier, one hop away
                assert when[(lg - 1, j)] == t - 1, (lg, j)
        assert max(when.values()) == ticks - 1

    def test_bubble_and_cuts_model(self):
        from repro.transport.schedules import get_schedule
        g = get_schedule("gpipe")
        i2 = get_schedule("interleaved", 2)
        assert g.bubble_fraction(8, 4) == pytest.approx(3 / 11)
        assert i2.bubble_fraction(8, 4) == pytest.approx(3 / 19)
        assert i2.bubble_fraction(8, 4) < g.bubble_fraction(8, 4)
        assert g.wire_cuts(4) == 3 and i2.wire_cuts(4) == 7
        f = get_schedule("1f1b")
        assert f.bubble_fraction(8, 4) == g.bubble_fraction(8, 4)
        assert f.stash_microbatches(16, 4) == 4
        assert g.stash_microbatches(16, 4) == 16

    def test_registry_and_validation(self):
        from repro.transport.schedules import (as_schedule, get_schedule)
        with pytest.raises(ValueError):
            get_schedule("zero-bubble")
        with pytest.raises(ValueError):
            get_schedule("gpipe", 2).validate(4, 2)
        with pytest.raises(ValueError):
            get_schedule("1f1b", 2).validate(4, 2)
        with pytest.raises(ValueError):
            get_schedule("interleaved", 2).validate(3, 2)
        s = get_schedule("interleaved", 2)
        assert as_schedule(s) is s
        with pytest.raises(ValueError):
            as_schedule(s, virtual_stages=3)

    def test_nonpositive_microbatches_rejected(self):
        """Satellite: microbatches=0 used to silently mean 'stage count'."""
        from repro.transport.pipeline import pipeline_apply
        mesh = make_mesh((1,), ("stage",))
        params = {"w": jnp.zeros((1, 4, 4))}
        x = jnp.zeros((4, 4))
        fn = lambda p, h: h @ p["w"]
        for bad in (0, -1, 2.5):
            with pytest.raises(ValueError, match="positive"):
                pipeline_apply(fn, params, x, mesh, "stage",
                               microbatches=bad)

    def test_params_leading_dim_checked(self):
        from repro.transport.pipeline import pipeline_apply
        mesh = make_mesh((1,), ("stage",))
        params = {"w": jnp.zeros((3, 4, 4))}    # not S*v = 2
        x = jnp.zeros((4, 4))
        with pytest.raises(ValueError, match="leading dim"):
            pipeline_apply(lambda p, h: h @ p["w"], params, x, mesh,
                           "stage", schedule="interleaved",
                           virtual_stages=2)


class TestFusedPayload:
    @pytest.mark.parametrize("scheme", ("none", "q8", "q4", "topk"))
    def test_fuse_roundtrip_bitwise(self, scheme):
        from repro.transport.codecs import fuse_payload, unfuse_payload
        x = _x((4, 33), jnp.float32)
        p = pack_payload(x, scheme, 0.1)
        buf = fuse_payload(p)
        assert buf.dtype == jnp.uint8
        assert buf.size == wire_bytes(p)          # byte-identical wire cost
        q = unfuse_payload(buf, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), p))
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_schedule_1f1b_matches_gpipe_subprocess():
    """Satellite: 1F1B == GPipe bit-for-bit (loss + grads, none and q8,
    microbatches >> stages) and interleaved rejects mb % S != 0."""
    r = _run_sub(SCHEDULE_EQUIV_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SCHEDULE_EQUIV_OK" in r.stdout


@pytest.mark.slow
def test_schedule_interleaved_matches_references_subprocess():
    """Acceptance (run explicitly in CI): interleaved(v=2) == composed
    GPipe bit-for-bit at scheme='none' on bf16, matches the per-cut
    simulated boundary to 1e-4 with q8 (loss + grads), and the
    chunk-indexed EF21 feedback buffers (send + delta-coded mirrors, both
    directions) match the per-cut simulated boundary."""
    r = _run_sub(SCHEDULE_INTERLEAVED_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SCHEDULE_INTERLEAVED_OK" in r.stdout


@pytest.mark.slow
def test_schedule_1f1b_feedback_matches_simulated_subprocess():
    """Acceptance (run explicitly in CI): EF/AQ-SGD buffers under the
    1F1B schedule match the simulated boundary step-for-step."""
    r = _run_sub(SCHEDULE_FEEDBACK_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SCHEDULE_FEEDBACK_OK" in r.stdout
