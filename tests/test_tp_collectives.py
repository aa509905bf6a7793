"""Compressed tensor-parallel collectives (transport/tp_collectives.py).

In-process: mesh constructors, TPCollectives/init_tp_state validation,
the tp=1 degenerate passthrough, and the exact-vs-model wire cost of
``tp_wire_report`` per codec.  Subprocesses (forced host devices): rings
of four against a source-ordered oracle and the lowering gate on payload
banking, the tp=2 toy acceptance — codec="none" training BIT-IDENTICAL to
a blocked rank-ordered solo reference, q8+EF tracking it step for step —
the LM DPxTP step behind ``parallel=ParallelSpec``, and the 8-device
2x2x2 (data, stage, tensor) pipeline run.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.launch.mesh import (make_3d_mesh, make_local_mesh,
                               make_tensor_mesh)
from repro.transport.codecs import wire_bytes
from repro.transport.tp_collectives import (TP_FEEDBACK_MODES,
                                            TPCollectives, init_tp_state,
                                            tp_apply, tp_payload_struct,
                                            tp_wire_report)


class TestMeshes:
    def test_3d_axis_names_and_shape(self):
        mesh = make_3d_mesh(1, 1, 1)
        assert mesh.axis_names == ("data", "stage", "tensor")
        assert dict(mesh.shape) == {"data": 1, "stage": 1, "tensor": 1}

    def test_local_mesh_uses_canonical_names(self):
        assert make_local_mesh().axis_names == ("data", "tensor")

    def test_bad_sizes_rejected(self):
        with pytest.raises(ValueError, match="tp"):
            make_tensor_mesh(0)
        with pytest.raises(ValueError, match="dp"):
            make_3d_mesh(0, 1, 1)

    def test_insufficient_devices(self):
        n = jax.device_count()
        with pytest.raises(RuntimeError, match="devices"):
            make_tensor_mesh(n + 1)
        with pytest.raises(RuntimeError, match="devices"):
            make_3d_mesh(n + 1, 1, 1)


class TestValidation:
    def test_tp_feedback_modes_are_the_tp_scoped_registry(self):
        assert set(TP_FEEDBACK_MODES) == {"none", "ef", "ef21"}

    def test_unknown_feedback_rejected(self):
        with pytest.raises(ValueError, match="unknown tp feedback"):
            TPCollectives(make_tensor_mesh(1), "tensor", codec="q8",
                          feedback="momentum")
        with pytest.raises(ValueError, match="unknown tp feedback"):
            init_tp_state((4, 8, 16), 2, "aqsgd")  # boundary-only mode

    def test_feedback_needs_a_lossy_codec(self):
        with pytest.raises(ValueError, match="nothing to compensate"):
            TPCollectives(make_tensor_mesh(1), "tensor", codec="none",
                          feedback="ef")

    def test_state_buffers_per_mode(self):
        feat, sites = (4, 8, 16), 3
        none = init_tp_state(feat, sites, "none")
        assert none.resid.size == 0 and none.mirror.size == 0
        ef = init_tp_state(feat, sites, "ef")
        assert ef.resid.shape == (sites, *feat) and ef.mirror.size == 0
        ef21 = init_tp_state(feat, sites, "ef21")
        assert ef21.mirror.shape == (sites, *feat) and ef21.resid.size == 0
        assert ef.scope == "tp"

    def test_wire_report_rejects_indivisible_seq(self):
        with pytest.raises(ValueError, match="not divisible"):
            tp_wire_report((4, 63, 32), 2, "q8")


class TestWireReport:
    FEAT = (4, 64, 32)

    @pytest.mark.parametrize("codec", ("none", "q8", "q4", "topk"))
    def test_exact_matches_cost_model(self, codec):
        rep = tp_wire_report(self.FEAT, 2, codec, k_frac=0.25)
        exact, model = rep["payload_bytes_per_hop"], rep["model_bytes"]
        assert abs(exact - model) <= 64 + 0.005 * model, rep
        assert rep["hops_per_collective"] == 1
        assert rep["wire_bytes_per_collective"] == exact
        assert rep["wire_bytes_per_forward"] == 2 * exact

    def test_compression_orders_bytes(self):
        by = {c: tp_wire_report(self.FEAT, 2, c)["payload_bytes_per_hop"]
              for c in ("none", "q8", "q4")}
        assert by["q4"] < by["q8"] < by["none"]

    def test_hops_scale_with_ring(self):
        r4 = tp_wire_report(self.FEAT, 4, "q8", sites=3)
        assert r4["hops_per_collective"] == 3
        assert (r4["wire_bytes_per_forward"]
                == 3 * 2 * 3 * r4["payload_bytes_per_hop"])

    def test_payload_struct_none_is_raw_bf16(self):
        shard = (4, 32, 32)
        struct = tp_payload_struct(shard, "none")
        assert wire_bytes(struct) == int(np.prod(shard)) * 2

    def test_collectives_wire_report_delegates(self):
        tpc = TPCollectives(make_tensor_mesh(1), "tensor", codec="q8")
        rep = tpc.wire_report(self.FEAT, sites=2)
        assert rep["tp"] == 1 and rep["hops_per_collective"] == 0
        assert rep["sites_per_forward"] == 2


def _mlp_stage_fn(tpc):
    """gather -> gelu MLP on the full activation -> reduce-scatter."""

    def fn(p, xl, rs, ms):
        if tpc.feedback == "ef":
            full, buf = tpc.gather_site(xl, rs[0])
            rs = rs.at[0].set(buf)
        else:
            full, _ = tpc.gather_site(xl, None)
        y = tpc.scatter(jax.nn.gelu(full @ p["w1"]) @ p["w2"])
        return y, rs, ms

    return fn


class TestTp1Passthrough:
    @pytest.mark.parametrize("codec", ("none", "q8"))
    def test_tp1_apply_is_identity(self, codec):
        """A 1-wide ring never packs: gather/scatter are exact even with a
        lossy codec configured, so solo programs are untouched."""
        tpc = TPCollectives(make_tensor_mesh(1), "tensor", codec=codec)
        rng = np.random.RandomState(0)
        d, f = 16, 32
        params = {"w1": jnp.asarray(rng.randn(d, f), jnp.float32),
                  "w2": jnp.asarray(rng.randn(f, d), jnp.float32)}
        x = jnp.asarray(rng.randn(2, 8, d), jnp.float32)
        y, _ = tp_apply(_mlp_stage_fn(tpc), params, x, tpc,
                        param_dims={"w1": 1, "w2": 0}, sites=1)
        ref = jax.nn.gelu(x @ params["w1"]) @ params["w2"]
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


# ---------------------------------------------------------------------------
# Subprocess acceptance (forced host devices)
# ---------------------------------------------------------------------------

TOY_TP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_tensor_mesh
    from repro.transport.tp_collectives import (TPCollectives,
                                                init_tp_state, tp_apply)

    TP, B, S, D, F, LR, STEPS = 2, 4, 16, 32, 64, 0.05, 4
    mesh = make_tensor_mesh(TP)
    rng = np.random.RandomState(0)
    w1 = jnp.asarray(rng.randn(D, F) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.randn(F, D) * 0.1, jnp.float32)
    x = jnp.asarray(rng.randn(B, S, D), jnp.float32)
    tgt = jnp.asarray(rng.randn(B, S, D), jnp.float32)

    def stage_fn(tpc):
        def fn(p, xl, rs, ms):
            if tpc.feedback == "ef":
                full, buf = tpc.gather_site(xl, rs[0])
                rs = rs.at[0].set(buf)
            else:
                full, _ = tpc.gather_site(xl, None)
            y = tpc.scatter(jax.nn.gelu(full @ p["w1"]) @ p["w2"])
            return y, rs, ms
        return fn

    def run_tp(codec, feedback="none"):
        tpc = TPCollectives(mesh, "tensor", codec=codec, feedback=feedback)
        state = init_tp_state((B, S, D), 1, feedback)
        fn = stage_fn(tpc)

        @jax.jit
        def step(params, state):
            def loss_fn(p):
                y, ns = tp_apply(fn, p, x, tpc,
                                 param_dims={"w1": 1, "w2": 0},
                                 state=state, sites=1)
                return jnp.mean((y - tgt) ** 2), ns
            (loss, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params)
            new = jax.tree.map(lambda p, d: p - LR * d, params, g)
            return new, ns, loss

        params, losses = {"w1": w1, "w2": w2}, []
        for _ in range(STEPS):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        return losses, params

    def run_solo():
        # the blocked rank-ordered reference: same LOCAL matmul shapes,
        # partial outputs summed in source-rank order s=0..tp-1 (every
        # sum is 2-term at tp=2, so association matches the wire's)
        f = F // TP

        @jax.jit
        def step(params):
            def loss_fn(p):
                y = None
                for s in range(TP):
                    h = jax.nn.gelu(x @ p["w1"][:, s * f:(s + 1) * f])
                    part = h @ p["w2"][s * f:(s + 1) * f, :]
                    y = part if y is None else y + part
                return jnp.mean((y - tgt) ** 2)
            loss, g = jax.value_and_grad(loss_fn)(params)
            new = jax.tree.map(lambda p, d: p - LR * d, params, g)
            return new, loss

        params, losses = {"w1": w1, "w2": w2}, []
        for _ in range(STEPS):
            params, loss = step(params)
            losses.append(float(loss))
        return losses, params

    # Forward pass: BITWISE.  Every wire op is a raw passthrough, the
    # gather concatenates in source-rank order and every reduce-scatter
    # sum is 2-term at tp=2, so the association matches the reference's.
    tpc0 = TPCollectives(mesh, "tensor", codec="none")
    fn0 = stage_fn(tpc0)

    @jax.jit
    def tp_fwd(params):
        y, _ = tp_apply(fn0, params, x, tpc0,
                        param_dims={"w1": 1, "w2": 0}, sites=1)
        return y

    f = F // TP

    @jax.jit
    def ref_fwd(params):
        y = None
        for s in range(TP):
            h = jax.nn.gelu(x @ params["w1"][:, s * f:(s + 1) * f])
            part = h @ params["w2"][s * f:(s + 1) * f, :]
            y = part if y is None else y + part
        return y

    assert np.array_equal(np.asarray(tp_fwd({"w1": w1, "w2": w2})),
                          np.asarray(ref_fwd({"w1": w1, "w2": w2})))
    print("TOY_TP_FWD_BITWISE_OK")

    # Training: ulp-level.  The wire adds NO error (w2's gradient comes
    # back bit-identical), but XLA may tile the dw1 dot_general's B*S
    # reduction differently across the two programs, and GSPMD reduces
    # the sharded scalar mean with a different association — both last-
    # ulp float effects, not codec loss.
    ref_losses, ref_params = run_solo()
    tp_losses, tp_params = run_tp("none")
    np.testing.assert_allclose(tp_losses, ref_losses, rtol=1e-5, atol=0)
    for k in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(tp_params[k]), np.asarray(ref_params[k]),
            rtol=1e-5, atol=1e-7, err_msg=k)
    print("TOY_TP_TRAIN_OK")

    q8_losses, _ = run_tp("q8", feedback="ef")
    assert all(np.isfinite(q8_losses)), q8_losses
    assert q8_losses[-1] < q8_losses[0], q8_losses
    for a, b in zip(q8_losses, ref_losses):
        assert abs(a - b) <= 0.2 * max(abs(b), 1.0), (a, b)
    print("TOY_TP_EF_OK")
""")


LM_DP_TP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    from repro.configs.registry import get
    from repro.core.parallel import AxisSpec, ParallelSpec
    from repro.core.policy import NO_POLICY
    from repro.data.synthetic import LMData
    from repro.train.loop import run_lm_experiment

    cfg = get("gpt2-small", smoke=True)

    def curve(spec):
        data = LMData(num_train=24, seq_len=32)
        return run_lm_experiment(cfg, NO_POLICY, epochs=1, batch=8,
                                 data=data, parallel=spec).train_curve

    solo = curve(ParallelSpec())
    tp2 = curve(ParallelSpec({"tensor": 2}))
    assert all(np.isfinite(tp2)), tp2
    for a, b in zip(tp2, solo):
        assert abs(a - b) <= 0.05 * max(abs(b), 1.0), (tp2, solo)
    print("LM_TP2_NONE_OK")

    q8 = curve(ParallelSpec({"tensor": AxisSpec(size=2, codec="q8",
                                                feedback="ef")}))
    for a, b in zip(q8, solo):
        assert abs(a - b) <= 0.2 * max(abs(b), 1.0), (q8, solo)
    print("LM_TP2_Q8EF_OK")

    dptp = curve(ParallelSpec({"data": 2, "tensor": 2}))
    assert all(np.isfinite(dptp)) and dptp[-1] < dptp[0], dptp
    for a, b in zip(dptp, solo):
        assert abs(a - b) <= 0.2 * max(abs(b), 1.0), (dptp, solo)
    print("LM_DP2_TP2_OK")
""")


LM_3D_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.configs.registry import get
    from repro.core.parallel import AxisSpec, ParallelSpec
    from repro.core.policy import NO_POLICY
    from repro.data.synthetic import LMData
    from repro.train.loop import run_lm_experiment

    cfg = get("gpt2-small", smoke=True)

    def curve(spec):
        data = LMData(num_train=24, seq_len=32)
        return run_lm_experiment(cfg, NO_POLICY, epochs=1, batch=8,
                                 data=data, parallel=spec).train_curve

    ref = curve(ParallelSpec({"data": 2,
                              "stage": AxisSpec(size=2, codec="q8")}))
    full = curve(ParallelSpec({"data": 2,
                               "stage": AxisSpec(size=2, codec="q8"),
                               "tensor": AxisSpec(size=2, codec="q4")}))
    assert all(np.isfinite(full)) and full[-1] < full[0], full
    for a, b in zip(full, ref):
        assert abs(a - b) <= 0.2 * max(abs(b), 1.0), (full, ref)
    print("LM_3D_OK")
""")


# A ring of four: the association of the source-ordered sum matters here
# (at tp=2 every sum is two terms and commutes).  Each case is checked
# bitwise on every rank against a plain oracle that packs each source's
# tensor with the codec and sums the decoded values in source-rank order.
# The same process lowers the tp=2 q8 gather and reduce-scatter and the
# dp=2 q8 reduce and looks for banking of uint8 payloads by the traced
# rank: a scatter, or a dynamic (update-)slice of a ui8 buffer.

RING4_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import re
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.transport.base import shard_map_compat
    from repro.transport.codecs import get_codec
    from repro.transport.collectives import (init_dp_state,
                                             make_grad_all_reduce,
                                             pack_grad_leaf,
                                             unpack_grad_leaf)
    from repro.transport.tp_collectives import TPCollectives

    N, B, SL, D = 4, 2, 8, 16
    results = {}


    def check(name, fn):
        try:
            fn()
            results[name] = "ok"
        except Exception as e:                      # reported per case
            results[name] = f"{type(e).__name__}: {e}"[:2000]


    def roundtrip(codec, a, shape, dtype):
        # the plain oracle: pack one source's tensor, decode it
        return unpack_grad_leaf(codec, pack_grad_leaf(codec, a, 0.1),
                                shape).astype(dtype)


    def per_rank(tpc, op):
        # rank r's own input in, rank r's own output out (no replication)
        mesh = tpc.mesh
        return jax.jit(shard_map_compat(lambda a: op(a[0])[None], mesh,
                                        (P("tensor"),), P("tensor")))


    tmesh = make_mesh((N,), ("tensor",))
    key = jax.random.PRNGKey(0)
    shards = jax.random.normal(key, (N, B, SL, D)).astype(jnp.bfloat16)
    partials = jax.random.normal(jax.random.PRNGKey(1),
                                 (N, B, N * SL, D)).astype(jnp.bfloat16)
    for codec_name in ("none", "q8", "q4", "topk"):
        codec = get_codec(codec_name)
        for fused in (True, False):
            tag = f"{codec_name}-{'fused' if fused else 'unfused'}"
            tpc = TPCollectives(tmesh, "tensor", codec=codec_name, fused=fused)

            def gather():
                got = np.asarray(per_rank(tpc, tpc.all_gather_wire)(shards))
                want = jax.jit(lambda xs: jnp.concatenate(
                    [roundtrip(codec, xs[s], xs[s].shape, xs.dtype)
                     for s in range(N)], axis=1))(shards)
                for r in range(N):
                    np.testing.assert_array_equal(got[r], np.asarray(want))

            def scatter():
                got = np.asarray(
                    per_rank(tpc, tpc.reduce_scatter_wire)(partials))

                def oracle(ps, r):
                    out = None
                    for s in range(N):         # source-rank order
                        m = roundtrip(codec, ps[s][:, r * SL:(r + 1) * SL],
                                      (B, SL, D), ps.dtype)
                        out = m if out is None else out + m
                    return out
                for r in range(N):
                    want = jax.jit(oracle, static_argnums=1)(partials, r)
                    np.testing.assert_array_equal(got[r], np.asarray(want))

            check(f"gather-{tag}", gather)
            check(f"scatter-{tag}", scatter)

    dmesh = make_mesh((N,), ("data",))
    GRADS = {"w": jnp.zeros((4, 33)), "b": jnp.zeros((7,))}
    steps = [jax.tree.map(lambda a, i=i: jax.random.normal(
        jax.random.PRNGKey(10 + i), (N, *a.shape)), GRADS) for i in range(2)]
    codec = get_codec("q8")
    for fb in ("none", "ef"):
        def reduce(fb=fb):
            red = jax.jit(make_grad_all_reduce(dmesh, "data", "q8",
                                               feedback=fb))
            state = init_dp_state(GRADS, N, fb)
            e = {k: [jnp.zeros(a.shape, jnp.float32)] * N
                 for k, a in GRADS.items()}
            @jax.jit
            def oracle(g_dp, e):
                want, new_e = {}, {}
                for k, a in GRADS.items():
                    new_e[k] = []
                    for s in range(N):         # source-rank order
                        x = g_dp[k][s].astype(jnp.float32)
                        if fb == "ef":
                            x = x + e[k][s]
                        m = roundtrip(codec, x, a.shape, jnp.float32)
                        new_e[k].append(x - m)
                        want[k] = m if k not in want else want[k] + m
                return want, new_e

            for g_dp in steps:
                got, state = red(g_dp, state)
                want, new_e = oracle(g_dp, e)
                for k in GRADS:
                    np.testing.assert_array_equal(np.asarray(got[k]),
                                                  np.asarray(want[k]))
                    if fb == "ef":
                        np.testing.assert_array_equal(
                            np.asarray(state.resid[k]),
                            np.asarray(jnp.stack(new_e[k])))
                e = new_e
        check(f"dp-q8-{fb}", reduce)

    # lowering gate: no scatter, no dynamic (update-)slice of a uint8 buffer
    banking = re.compile(r"stablehlo\\.scatter"
                         r"|stablehlo\\.dynamic_(update_)?slice[^\\n]*ui8")
    mesh2 = make_mesh((2,), ("tensor",))
    tpc2 = TPCollectives(mesh2, "tensor", codec="q8")
    dmesh2 = make_mesh((2,), ("data",))
    red2 = make_grad_all_reduce(dmesh2, "data", "q8")
    lowered = {
        "tp-gather": per_rank(tpc2, tpc2.all_gather_wire).lower(shards[:2]),
        "tp-scatter": per_rank(tpc2, tpc2.reduce_scatter_wire).lower(
            partials[:2, :, :2 * SL]),
        "dp-reduce": jax.jit(red2).lower(
            jax.tree.map(lambda a: a[:2], steps[0]), init_dp_state(GRADS, 2)),
    }
    for name, low in lowered.items():
        hits = [m.group(0)[:120] for m in banking.finditer(low.as_text())]
        results[f"lower-{name}"] = ("ok" if not hits
                                    else f"{len(hits)}: {hits[:4]}")
    print("RING4 " + json.dumps(results))
""")

RING4_CASES = ([f"{op}-{codec}-{fusion}" for op in ("gather", "scatter")
                for codec in ("none", "q8", "q4", "topk")
                for fusion in ("fused", "unfused")]
               + ["dp-q8-none", "dp-q8-ef"])


@pytest.fixture(scope="module")
def ring4():
    r = _run_sub(RING4_SCRIPT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RING4 ")]
    assert line, r.stdout[-2000:]
    return json.loads(line[-1][len("RING4 "):])


@pytest.mark.parametrize("case", RING4_CASES)
def test_ring_of_four_matches_source_ordered_oracle(ring4, case):
    """tp=4 gather / reduce-scatter (every rank's output) and dp=4 q8
    reduce (EF over two steps, residuals included): bitwise the
    oracle's."""
    assert ring4[case] == "ok", ring4[case]


@pytest.mark.parametrize("ring", ("tp-gather", "tp-scatter", "dp-reduce"))
def test_ring_lowers_without_rank_indexed_uint8(ring4, ring):
    """No stablehlo.scatter and no dynamic_slice / dynamic_update_slice on
    a ui8 operand in the lowered q8 ring: received payloads stay in a
    list by hop, ordered by source rank only after decode."""
    assert ring4[f"lower-{ring}"] == "ok", ring4[f"lower-{ring}"]


def _run_sub(script):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.slow
def test_toy_tp_acceptance_subprocess():
    """tp=2 gelu-MLP: the uncompressed wire is BITWISE on the forward
    pass vs the blocked rank-ordered solo reference, training matches to
    the ulp, and q8+EF tracks the reference step for step."""
    r = _run_sub(TOY_TP_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TOY_TP_FWD_BITWISE_OK" in r.stdout
    assert "TOY_TP_TRAIN_OK" in r.stdout
    assert "TOY_TP_EF_OK" in r.stdout


@pytest.mark.slow
def test_lm_dp_tp_acceptance_subprocess():
    """2x1x2 DPxTP LM behind parallel=ParallelSpec: tp=2/none tracks solo
    tightly, q8+EF and the composed DPxTP mesh track it loosely."""
    r = _run_sub(LM_DP_TP_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr
    for tag in ("LM_TP2_NONE_OK", "LM_TP2_Q8EF_OK", "LM_DP2_TP2_OK"):
        assert tag in r.stdout, r.stdout


@pytest.mark.slow
def test_lm_3d_mesh_acceptance_subprocess():
    """All three axes at once (2x2x2, 8 devices): the q8-stage/q4-tensor
    pipeline trains and tracks the tp=1 pipeline reference."""
    r = _run_sub(LM_3D_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LM_3D_OK" in r.stdout
