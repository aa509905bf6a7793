"""DP gradient all-reduce (transport/collectives.py) + 2D mesh tests.

In-process tests run under plain ``jit`` on the single default device
(codec roundtrips on ragged/odd-sized parameter leaves — the q4 pad path —
mesh construction/validation, dp=1 reduce identities, EF semantics).  The
2x2 (dp=2, stages=2) acceptance runs in a subprocess with 4 forced host
devices: ``dp_codec=none`` training is BIT-IDENTICAL to the serial
single-replica reference, compressed reduces track it within tolerance,
and per-reduce wire bytes match each codec's cost model.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.compressors import quantize_dequantize, topk_compress
from repro.launch.mesh import make_data_mesh, make_dp_pipeline_mesh
from repro.transport.codecs import (fuse_payload, get_codec, unfuse_payload,
                                    wire_bytes)
from repro.transport.collectives import (dp_wire_report, grad_payload_structs,
                                         init_dp_state, make_grad_all_reduce,
                                         pack_grad_leaf, unpack_grad_leaf)


def _ragged_tree(seed=0):
    """Odd/ragged parameter-leaf shapes: odd flat n (q4 pad path), a
    rank-3 stack, a scalar-ish vector, and a bf16 leaf."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "w": jax.random.normal(ks[0], (2, 16, 32), jnp.float32),
        "gamma": jax.random.normal(ks[1], (33,), jnp.float32),
        "b": jax.random.normal(ks[2], (7,), jnp.float32),
        "h": jax.random.normal(ks[3], (3, 5), jnp.float32)
            .astype(jnp.bfloat16),
    }


class TestDPMesh:
    def test_data_mesh_axis_and_size(self):
        m = make_data_mesh(1)
        assert m.axis_names == ("data",) and m.shape["data"] == 1

    def test_dp_pipeline_mesh_axes(self):
        m = make_dp_pipeline_mesh(1, 1)
        assert m.axis_names == ("data", "stage")
        assert m.shape == {"data": 1, "stage": 1}
        m2 = make_dp_pipeline_mesh(1, 1, data_axis="dp", stage_axis="pp")
        assert m2.axis_names == ("dp", "pp")

    def test_invalid_sizes_rejected(self):
        with pytest.raises(ValueError, match=">= 1"):
            make_dp_pipeline_mesh(0, 2)
        with pytest.raises(ValueError, match=">= 1"):
            make_data_mesh(0)

    def test_insufficient_devices_rejected(self):
        need = jax.device_count() + 1
        with pytest.raises(RuntimeError, match="devices"):
            make_data_mesh(need)
        with pytest.raises(RuntimeError, match="DPxPP mesh"):
            make_dp_pipeline_mesh(need, 1)


class TestGradPackRoundtrip:
    """Codec roundtrips on ragged/odd-sized parameter leaves, plain jit."""

    def test_none_is_raw_passthrough_bitwise(self):
        codec = get_codec("none")
        for leaf in jax.tree.leaves(_ragged_tree()):
            p = pack_grad_leaf(codec, leaf)
            y = unpack_grad_leaf(codec, p, leaf.shape)
            assert y.dtype == leaf.dtype        # no bf16 downcast
            np.testing.assert_array_equal(np.asarray(y), np.asarray(leaf))

    @pytest.mark.parametrize("bits", (4, 8))
    def test_quant_matches_dense_compressor_on_odd_leaves(self, bits):
        """Per-leaf per-tensor scales; the 33-element leaf hits the q4
        odd-dim pad path."""
        codec = get_codec(f"q{bits}")
        for leaf in jax.tree.leaves(_ragged_tree()):
            p = pack_grad_leaf(codec, leaf)
            y = unpack_grad_leaf(codec, p, leaf.shape)
            flat = leaf.reshape(1, -1).astype(jnp.float32)
            ref = quantize_dequantize(flat, bits).reshape(leaf.shape)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))

    def test_topk_support_matches_dense_compressor(self):
        codec = get_codec("topk")
        for leaf in jax.tree.leaves(_ragged_tree()):
            p = pack_grad_leaf(codec, leaf, 0.3)
            y = unpack_grad_leaf(codec, p, leaf.shape)
            flat = leaf.reshape(1, -1).astype(jnp.float32)
            ref = topk_compress(flat, 0.3).reshape(leaf.shape)
            assert (np.asarray(y != 0) == np.asarray(ref != 0)).all()
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                       rtol=1e-2, atol=1e-2)

    def test_topk_idx_dtype_per_leaf(self):
        """Ragged leaves pick their index dtype independently."""
        codec = get_codec("topk")
        small = jnp.zeros((33,)).at[3].set(1.0)
        big = jnp.zeros(((1 << 16) + 8,)).at[70000].set(1.0)
        assert pack_grad_leaf(codec, small, 0.1)["idx"].dtype == jnp.uint16
        assert pack_grad_leaf(codec, big, 0.001)["idx"].dtype == jnp.int32

    @pytest.mark.parametrize("codec_name", ("none", "q8", "q4", "topk"))
    def test_fused_payload_roundtrip_bitwise(self, codec_name):
        """All leaf payloads fuse into ONE uint8 buffer, byte-identical."""
        codec = get_codec(codec_name)
        tree = _ragged_tree()
        payloads = [pack_grad_leaf(codec, a, 0.3)
                    for a in jax.tree.leaves(tree)]
        buf = fuse_payload(payloads)
        assert buf.dtype == jnp.uint8
        assert buf.size == wire_bytes(payloads)
        struct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), payloads)
        back = unfuse_payload(buf, struct)
        for a, b in zip(jax.tree.leaves(payloads), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("codec_name", ("none", "q8", "q4", "topk"))
    def test_wire_report_matches_cost_model(self, codec_name):
        tree = _ragged_tree()
        rep = dp_wire_report(tree, codec_name, k_frac=0.3, dp=2)
        slack = 16 * rep["n_param_leaves"] + 0.01 * max(rep["model_bytes"],
                                                        1)
        assert abs(rep["payload_bytes_per_hop"]
                   - rep["model_bytes"]) <= slack, rep
        assert rep["wire_bytes_per_reduce"] == \
            (rep["dp"] - 1) * rep["payload_bytes_per_hop"]
        structs = grad_payload_structs(tree, codec_name, 0.3)
        assert rep["payload_bytes_per_hop"] == wire_bytes(structs)
        if codec_name == "none":
            raw = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(tree))
            assert rep["payload_bytes_per_hop"] == raw == rep["model_bytes"]


class TestDPStateAndValidation:
    def test_state_structure(self):
        tree = _ragged_tree()
        st = init_dp_state(tree, 2, "none")
        assert st.resid.shape == (2, 0) and st.agg.shape == (0,)
        st = init_dp_state(tree, 3, "ef")
        assert st.resid["w"].shape == (3, 2, 16, 32)
        assert st.agg.shape == (0,)
        st = init_dp_state(tree, 2, "ef21")
        assert st.agg["gamma"].shape == (33,)

    def test_unknown_feedback_rejected(self):
        with pytest.raises(ValueError, match="unknown dp feedback"):
            init_dp_state(_ragged_tree(), 2, "aqsgd")
        mesh = make_data_mesh(1)
        with pytest.raises(ValueError, match="unknown dp feedback"):
            make_grad_all_reduce(mesh, "data", "q8", feedback="momentum")

    def test_feedback_requires_lossy_codec(self):
        mesh = make_data_mesh(1)
        with pytest.raises(ValueError, match="LOSSY"):
            make_grad_all_reduce(mesh, "data", "none", feedback="ef")


class TestAllReduceSingleReplica:
    """dp=1 semantics under plain jit: the reduce degenerates to the
    codec roundtrip, EF residuals accumulate exactly."""

    def test_none_is_identity_bitwise(self):
        mesh = make_data_mesh(1)
        fn = make_grad_all_reduce(mesh, "data", "none")
        tree = _ragged_tree()
        g_dp = jax.tree.map(lambda a: a[None], tree)
        st = init_dp_state(tree, 1, "none")
        reduced, st2 = jax.jit(fn)(g_dp, st)
        for a, b in zip(jax.tree.leaves(reduced), jax.tree.leaves(tree)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert st2.resid.shape == (1, 0)

    def test_q8_is_codec_roundtrip(self):
        mesh = make_data_mesh(1)
        fn = make_grad_all_reduce(mesh, "data", "q8")
        tree = _ragged_tree()
        codec = get_codec("q8")
        reduced, _ = jax.jit(fn)(jax.tree.map(lambda a: a[None], tree),
                                 init_dp_state(tree, 1, "none"))
        for got, leaf in zip(jax.tree.leaves(reduced),
                             jax.tree.leaves(tree)):
            ref = unpack_grad_leaf(codec, pack_grad_leaf(codec, leaf),
                                   leaf.shape).astype(leaf.dtype)
            # fused in-shard_map dequant vs eager: fma rounding only
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(ref, np.float32),
                atol=1e-6, rtol=1e-5)

    def test_ef_residual_accumulates(self):
        """e' = g + e - C(g + e): after one reduce the residual holds the
        compression error; a second reduce of the SAME gradient sends the
        compensated message, driving cumulative error toward zero."""
        mesh = make_data_mesh(1)
        fn = jax.jit(make_grad_all_reduce(mesh, "data", "topk",
                                          k_frac=0.25, feedback="ef"))
        tree = {"w": _ragged_tree()["w"]}
        g_dp = jax.tree.map(lambda a: a[None], tree)
        st = init_dp_state(tree, 1, "ef")
        r1, st = fn(g_dp, st)
        e = np.asarray(st.resid["w"][0])
        np.testing.assert_allclose(
            e, np.asarray(tree["w"]) - np.asarray(r1["w"]), atol=1e-5)
        r2, st = fn(g_dp, st)
        got2 = np.asarray(r1["w"]) + np.asarray(r2["w"])
        want2 = 2 * np.asarray(tree["w"])
        err1 = np.abs(np.asarray(tree["w"]) - np.asarray(r1["w"])).sum()
        err2 = np.abs(want2 - got2).sum()
        assert err2 < 2 * err1          # residual stays bounded, no blow-up
        # and the classic EF telescoping: g1 + g2 - (m1 + m2) == e2
        np.testing.assert_allclose(np.asarray(st.resid["w"][0]),
                                   want2 - got2, atol=1e-4)

    def test_ef21_aggregate_tracks_reduced(self):
        mesh = make_data_mesh(1)
        fn = jax.jit(make_grad_all_reduce(mesh, "data", "q4",
                                          feedback="ef21"))
        tree = {"w": _ragged_tree()["w"], "gamma": _ragged_tree()["gamma"]}
        g_dp = jax.tree.map(lambda a: a[None], tree)
        st = init_dp_state(tree, 1, "ef21")
        r1, st = fn(g_dp, st)
        for k in tree:
            # G' == reduced, and w_r' == G' with one replica
            np.testing.assert_allclose(np.asarray(st.agg[k]),
                                       np.asarray(r1[k]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(st.resid[k][0]),
                                       np.asarray(r1[k]), atol=1e-5)
        # repeated identical grads converge: C(g - w) has shrinking error
        r2, st = fn(g_dp, st)
        d2 = max(float(np.abs(np.asarray(r2[k])
                              - np.asarray(tree[k])).max()) for k in tree)
        d1 = max(float(np.abs(np.asarray(r1[k])
                              - np.asarray(tree[k])).max()) for k in tree)
        assert d2 <= d1 + 1e-6, (d1, d2)


# ---------------------------------------------------------------------------
# 2x2 DPxPP acceptance (subprocess: 4 host devices)
# ---------------------------------------------------------------------------

DP_ACCEPT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_dp_pipeline_mesh, make_mesh
    from repro.transport.pipeline import pipeline_apply
    from repro.transport.collectives import (dp_wire_report, init_dp_state,
                                             make_grad_all_reduce)

    DP, S, B, D, MB = 2, 2, 8, 16, 2
    mesh = make_dp_pipeline_mesh(DP, S)
    mesh1 = make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    params0 = {"w1": jax.random.normal(k1, (S, D, 2 * D)) * 0.1,
               "w2": jax.random.normal(k2, (S, 2 * D, D)) * 0.1}
    stage_fn = lambda p, h: h + jnp.tanh(h @ p["w1"]) @ p["w2"]
    LR = 0.05

    def make_dp_step(codec, feedback):
        reduce_fn = make_grad_all_reduce(mesh, "data", codec, k_frac=0.3,
                                         feedback=feedback)

        @jax.jit
        def step(params, dp_state, x):
            pdp = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (DP, *a.shape)), params)

            def loss_fn(pdp):
                y = pipeline_apply(stage_fn, pdp, x, mesh, "stage",
                                   scheme="q8", microbatches=MB,
                                   dp_axis="data")
                return jnp.sum(y.astype(jnp.float32) ** 2) / B
            loss, g_dp = jax.value_and_grad(loss_fn)(pdp)
            g, new_dp = reduce_fn(g_dp, dp_state)
            params = jax.tree.map(lambda p, gg: p - LR * gg, params, g)
            return params, new_dp, loss
        return step

    def run_dp(codec, steps, feedback="none"):
        step = make_dp_step(codec, feedback)
        dp_state = init_dp_state(params0, DP, feedback)
        params, losses = params0, []
        rng = np.random.RandomState(0)
        for t in range(steps):
            x = jnp.asarray(rng.randn(B, D), jnp.float32)
            params, dp_state, l = step(params, dp_state, x)
            losses.append(float(l))
        return losses, params

    def run_serial(steps):
        '''Single-replica reference: the SAME per-shard pipeline program
        on a stages-only mesh, shard gradients summed serially.'''
        @jax.jit
        def step(params, x):
            def shard_loss(p, xs):
                y = pipeline_apply(stage_fn, p, xs, mesh1, "stage",
                                   scheme="q8", microbatches=MB)
                return jnp.sum(y.astype(jnp.float32) ** 2) / B
            ltot, g = 0.0, None
            for r in range(DP):
                xs = x[r * (B // DP):(r + 1) * (B // DP)]
                l, gr = jax.value_and_grad(shard_loss)(params, xs)
                ltot = ltot + l
                g = gr if g is None else jax.tree.map(jnp.add, g, gr)
            params = jax.tree.map(lambda p, gg: p - LR * gg, params, g)
            return params, ltot
        params, losses = params0, []
        rng = np.random.RandomState(0)
        for t in range(steps):
            x = jnp.asarray(rng.randn(B, D), jnp.float32)
            params, l = step(params, x)
            losses.append(float(l))
        return losses, params

    # (a) dp_codec=none == single-replica training BIT-FOR-BIT, through
    # the q8-compressed activation pipeline: both regimes live on one mesh
    dl, dparams = run_dp("none", 8)
    sl, sparams = run_serial(8)
    assert dl == sl, (dl, sl)
    for k in dparams:
        assert np.array_equal(np.asarray(dparams[k]), np.asarray(sparams[k])), k
    print("dp=none bitwise == serial reference:", dl[-1])

    # (b) compressed DP reduces track the uncompressed trajectory
    # step-for-step within tolerance
    for codec, fb, tol in (("q8", "none", 0.02), ("topk", "ef", 0.15),
                           ("q4", "ef21", 0.15)):
        cl, _ = run_dp(codec, 8, fb)
        for t, (a, b) in enumerate(zip(cl, dl)):
            assert abs(a - b) <= tol * max(abs(b), 1.0), \\
                (codec, fb, t, cl, dl)
        assert cl[-1] < cl[0], (codec, cl)
        print(codec, "+", fb, "tracks uncompressed:", cl[-1], dl[-1])

    # (c) wire bytes per reduce match each codec's wire_bytes_per_elem
    for codec in ("none", "q8", "q4", "topk"):
        rep = dp_wire_report(params0, codec, k_frac=0.3, dp=DP)
        slack = 16 * rep["n_param_leaves"] + 0.01 * rep["model_bytes"]
        assert abs(rep["payload_bytes_per_hop"]
                   - rep["model_bytes"]) <= slack, rep
        assert rep["wire_bytes_per_reduce"] == rep["payload_bytes_per_hop"]
        print(codec, "wire bytes/reduce:", rep["wire_bytes_per_reduce"])

    print("DP_ACCEPT_OK")
""")


LM_DP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.configs.registry import get
    from repro.core.policy import CompressionPolicy, NO_POLICY, quant_policy
    from repro.models import transformer
    from repro.optim.optimizers import OptimizerConfig, init_opt_state
    from repro.train.loop import init_lm_dp_state
    from repro.train.steps import make_lm_train_step

    cfg = get("gpt2-small", smoke=True)
    B, SEQ = 8, 32
    opt = OptimizerConfig(kind="adamw", lr=1e-3, weight_decay=0.0,
                          schedule="constant")
    params0 = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    toks = [rng.randint(0, 64, size=(B, SEQ)) for _ in range(4)]

    def run(dp, transport, dp_codec="none", grad_accum=1, stages=2):
        policy = (CompressionPolicy(num_stages=stages,
                                    boundary=quant_policy(8, 8))
                  if transport == "pipeline" else NO_POLICY)
        step = make_lm_train_step(cfg, policy, opt, remat=False,
                                  donate=False, transport=transport,
                                  grad_accum=grad_accum, dp=dp,
                                  dp_codec=dp_codec)
        params = jax.tree.map(jnp.asarray, params0)
        opt_state = init_opt_state(opt, params)
        dp_state = (init_lm_dp_state(cfg, params, policy, dp,
                                     transport=transport)
                    if dp > 1 else None)
        losses, bstates = [], []
        for t in toks:
            batch = {"tokens": jnp.asarray(t)}
            ids = jnp.zeros((B,), jnp.int32)
            if dp > 1:
                params, opt_state, bstates, dp_state, m = step(
                    params, opt_state, bstates, batch, ids, dp_state)
            else:
                params, opt_state, bstates, m = step(
                    params, opt_state, bstates, batch, ids)
            losses.append(float(m["loss"]))
        return losses

    # simulated transport: dp=2 vmap lanes + uncompressed reduce == the
    # single-replica step to float accumulation error; grad-accum composes
    base = run(1, "simulated")
    for tag, losses in [("dp2", run(2, "simulated")),
                        ("dp2+accum2", run(2, "simulated", grad_accum=2)),
                        ("dp2+q8", run(2, "simulated", dp_codec="q8"))]:
        for t, (a, b) in enumerate(zip(losses, base)):
            tol = 1e-3 if tag != "dp2+q8" else 0.02
            assert abs(a - b) <= tol * max(abs(b), 1.0), \\
                (tag, t, losses, base)
        print(tag, "tracks single-replica:", losses[-1], base[-1])

    # pipeline transport on the 2D mesh: q8 activations + q8 DP gradients
    pl = run(2, "pipeline", dp_codec="q8")
    assert all(np.isfinite(pl)), pl
    assert pl[-1] < pl[0], pl
    print("2D mesh q8+q8 LM training decreases:", pl)
    print("LM_DP_OK")
""")


def _run_sub(script):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.slow
def test_dp_pipeline_matches_serial_reference_subprocess():
    """Acceptance (run explicitly in CI, 4 host devices): on the 2x2
    (dp=2, stages=2) mesh, dp_codec=none training is bit-identical to the
    serial single-replica reference; q8 / topk+EF / q4+EF21 DP reduces
    track the uncompressed trajectory step-for-step; per-reduce wire
    bytes match each codec's ``wire_bytes_per_elem``."""
    r = _run_sub(DP_ACCEPT_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DP_ACCEPT_OK" in r.stdout


@pytest.mark.slow
def test_lm_train_step_dp_subprocess():
    """DP threading through train/steps.py: simulated-transport vmap
    lanes (+ grad-accum composition, + q8 reduce) track the
    single-replica step; the 2D DPxPP pipeline LM step trains."""
    r = _run_sub(LM_DP_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "LM_DP_OK" in r.stdout
