"""Kernel/jnp parity for the fused Pallas wire kernels (interpret mode).

Covers the four new kernel families and their transport-layer dispatch:

  * q4 pack/unpack (kernels/pack4.py)       — BIT-exact vs the jnp wire
    format, including odd feature dims (the in-kernel pad lane);
  * TopK select (kernels/topk_select.py)    — value/index SETS equal to
    ``lax.top_k`` modulo the documented tie order (ascending index vs
    descending value), dense scatter roundtrip bit-identical, and the
    uint16/int32 index boundary at n = 2**16 exactly;
  * payload framing (kernels/framing.py)    — byte-identical to the
    concat path, both directions;
  * DP decode+sum (kernels/dp_reduce.py)    — static rank-ordered fold:
    deterministic, replica-identical, and within 1 ulp of FMA rounding of
    the unfused reference loop;
  * ``unpack_payload`` exact key-SET dispatch + every registered codec's
    payload round-tripping through it;
  * the ``_pallas_tiling`` pow2 fix (kernels/tiling.py).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from conftest import hypothesis_or_stubs
given, settings, st = hypothesis_or_stubs()

import repro.core.compressors as C
from repro.kernels.tiling import (VMEM_BUDGET, column_tiling,
                                  pow2_row_block, wire_tiling)
from repro.transport import codecs


@pytest.fixture
def pallas_backend():
    prev = C.KERNEL_BACKEND
    C.KERNEL_BACKEND = "pallas"
    yield
    C.KERNEL_BACKEND = prev


def _pack_both(name, x, k_frac=0.25):
    """(jnp payload, pallas payload) for one codec."""
    prev = C.KERNEL_BACKEND
    try:
        C.KERNEL_BACKEND = "jnp"
        pj = codecs.get_codec(name).pack(x, k_frac)
        C.KERNEL_BACKEND = "pallas"
        pp = codecs.get_codec(name).pack(x, k_frac)
    finally:
        C.KERNEL_BACKEND = prev
    return pj, pp


# ---------------------------------------------------------------------------
# tiling (the _pallas_tiling satellite fix)
# ---------------------------------------------------------------------------

class TestTiling:
    def test_pow2_row_block(self):
        assert pow2_row_block(256) == 256
        assert pow2_row_block(48) == 16
        assert pow2_row_block(13) == 1      # prime: O(1), no O(m) scan
        assert pow2_row_block(1 << 20) == 256

    def test_wire_tiling_underfilled_returns_none(self):
        assert wire_tiling((12, 256)) is None      # pow2(12)=4 < 8 sublanes
        assert wire_tiling((13, 256)) is None      # prime m
        assert wire_tiling((2, 1024)) is None
        assert wire_tiling((1, 128)) is None       # the DP (1, n) leaves

    def test_wire_tiling_fits(self):
        assert wire_tiling((16, 256)) == (16, 256)
        assert wire_tiling((8, 128)) == (8, 128)
        assert wire_tiling((512, 384)) == (256, 128)

    def test_wire_tiling_non_lane_multiple(self):
        assert wire_tiling((16, 100)) is None

    def test_codecs_delegate(self):
        assert codecs._pallas_tiling((16, 256)) == wire_tiling((16, 256))
        assert codecs._pallas_tiling((13, 256)) is None

    def test_full_row_block_divides_and_fits(self):
        # column_tiling replaced full_row_block: every block is legal for
        # Mosaic (rows a multiple of 8 or the whole m, lanes a multiple
        # of 128), divides the operand and fits the VMEM budget
        for m in (1, 2, 12, 48, 256, 1000):
            for n in (128, 384, 4096, 786432):
                bm, bn = column_tiling(m, n)
                assert m % bm == 0 and (bm % 8 == 0 or bm == m)
                assert n % bn == 0 and bn % 128 == 0
                assert bm * bn * 4 <= VMEM_BUDGET or bn == 128
        assert column_tiling(8, 786432, max_lanes=32768) == (8, 32768)
        assert column_tiling(4, 512, lane_multiple=256) == (4, 512)


# ---------------------------------------------------------------------------
# q4: bit-exact, including odd feature dims
# ---------------------------------------------------------------------------

Q4_SHAPES = [(4, 255), (8, 129), (2, 7), (8, 256), (1, 33), (16, 512)]


class TestQ4Kernel:
    @pytest.mark.parametrize("shape", Q4_SHAPES)
    def test_pack_bit_exact(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape)
        pj, pp = _pack_both("q4", x)
        assert set(pj) == set(pp) == {"codes4", "min", "scale"}
        for k in pj:
            np.testing.assert_array_equal(np.asarray(pj[k]),
                                          np.asarray(pp[k]), err_msg=k)

    @pytest.mark.parametrize("shape", Q4_SHAPES)
    def test_unpack_parity(self, shape, pallas_backend):
        # bytes-on-wire are bit-exact (above); the fused dequant may round
        # 1 ulp tighter where the compiler emits an FMA for codes*sc+mn.
        x = jax.random.normal(jax.random.PRNGKey(1), shape)
        p = codecs.get_codec("q4").pack(x)
        got = codecs.get_codec("q4").unpack(p, x.shape, jnp.float32)
        C.KERNEL_BACKEND = "jnp"
        want = np.asarray(codecs.get_codec("q4").unpack(p, x.shape,
                                                        jnp.float32))
        tol = 1.2e-7 * max(float(np.abs(want).max()), 1.0)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=tol)

    def test_constant_tensor(self):
        x = jnp.full((4, 129), 3.25)
        pj, pp = _pack_both("q4", x)
        for k in pj:
            np.testing.assert_array_equal(np.asarray(pj[k]),
                                          np.asarray(pp[k]))

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 9),
           n=st.integers(1, 300))
    def test_property_bit_exact(self, seed, m, n):
        x = jax.random.normal(jax.random.PRNGKey(seed), (m, n)) \
            * jnp.exp(jax.random.normal(jax.random.PRNGKey(seed + 1), ()))
        pj, pp = _pack_both("q4", x)
        for k in pj:
            np.testing.assert_array_equal(np.asarray(pj[k]),
                                          np.asarray(pp[k]), err_msg=k)


# ---------------------------------------------------------------------------
# TopK: sets equal modulo documented tie order; u16/i32 boundary at 2**16
# ---------------------------------------------------------------------------

class TestTopKKernel:
    @pytest.mark.parametrize("shape,k_frac", [((4, 100), 0.25),
                                              ((8, 512), 0.1),
                                              ((2, 33), 0.5)])
    def test_sets_and_dense_roundtrip(self, shape, k_frac):
        x = jax.random.normal(jax.random.PRNGKey(2), shape)
        pj, pp = _pack_both("topk", x, k_frac)
        assert pj["idx"].shape == pp["idx"].shape
        assert pj["idx"].dtype == pp["idx"].dtype
        assert pj["vals"].dtype == pp["vals"].dtype == jnp.bfloat16
        for r in range(shape[0]):
            ij = set(np.asarray(pj["idx"][r]).tolist())
            ip = set(np.asarray(pp["idx"][r]).tolist())
            assert ij == ip, f"row {r}: index sets differ"
        dj = codecs.get_codec("topk").unpack(pj, x.shape, jnp.float32)
        dp = codecs.get_codec("topk").unpack(pp, x.shape, jnp.float32)
        np.testing.assert_array_equal(np.asarray(dj), np.asarray(dp))

    def test_exact_tie_handling(self):
        # more threshold ties than slots: the kernel must keep top_k's
        # lowest-index tie subset so the SET still matches exactly.
        x = jnp.array([[1.0, -2.0, 2.0, -2.0, 2.0, 0.5, -2.0, 0.0]])
        pj, pp = _pack_both("topk", x, 3 / 8)
        ij = set(np.asarray(pj["idx"][0]).tolist())
        ip = set(np.asarray(pp["idx"][0]).tolist())
        assert ij == ip == {1, 2, 3}

    @pytest.mark.parametrize("n,want_dtype", [(1 << 16, jnp.uint16),
                                              ((1 << 16) + 2, jnp.int32)])
    def test_index_dtype_boundary(self, n, want_dtype):
        x = jax.random.normal(jax.random.PRNGKey(3), (1, n))
        for backend in ("jnp", "pallas"):
            prev = C.KERNEL_BACKEND
            try:
                C.KERNEL_BACKEND = backend
                p = codecs.get_codec("topk").pack(x, 0.001)
            finally:
                C.KERNEL_BACKEND = prev
            assert p["idx"].dtype == want_dtype, backend
            d = codecs.get_codec("topk").unpack(p, x.shape, jnp.float32)
            kept = np.asarray(d != 0).sum()
            assert kept == max(1, int(round(0.001 * n))), backend

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           k=st.sampled_from([0.05, 0.1, 0.3, 0.5]),
           n=st.integers(4, 200))
    def test_property_set_parity(self, seed, k, n):
        x = jax.random.normal(jax.random.PRNGKey(seed), (3, n))
        pj, pp = _pack_both("topk", x, k)
        for r in range(3):
            assert (set(np.asarray(pj["idx"][r]).tolist())
                    == set(np.asarray(pp["idx"][r]).tolist()))
        dj = codecs.get_codec("topk").unpack(pj, x.shape, jnp.float32)
        dp = codecs.get_codec("topk").unpack(pp, x.shape, jnp.float32)
        np.testing.assert_array_equal(np.asarray(dj), np.asarray(dp))


# ---------------------------------------------------------------------------
# framing: byte-identical to the concat path
# ---------------------------------------------------------------------------

class TestFraming:
    PAYLOAD = {
        "a": jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 4),
        "b": jnp.array([True, False, True]),
        "c": jnp.arange(7, dtype=jnp.uint8),
        "d": jnp.arange(5, dtype=jnp.bfloat16),
    }

    def test_fuse_byte_identical(self, pallas_backend):
        fp = codecs.fuse_payload(self.PAYLOAD)
        C.KERNEL_BACKEND = "jnp"
        fj = codecs.fuse_payload(self.PAYLOAD)
        assert fp.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(fp), np.asarray(fj))

    def test_unfuse_roundtrip(self, pallas_backend):
        buf = codecs.fuse_payload(self.PAYLOAD)
        out = codecs.unfuse_payload(buf, self.PAYLOAD)
        assert set(out) == set(self.PAYLOAD)
        for k, v in self.PAYLOAD.items():
            assert out[k].dtype == v.dtype and out[k].shape == v.shape
            np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(v))

    def test_real_codec_payloads(self, pallas_backend):
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 129))
        for name in codecs.registered_codecs():
            p = codecs.get_codec(name).pack(x, 0.25)
            buf = codecs.fuse_payload(p)
            C.KERNEL_BACKEND = "jnp"
            ref = codecs.fuse_payload(p)
            C.KERNEL_BACKEND = "pallas"
            np.testing.assert_array_equal(np.asarray(buf), np.asarray(ref),
                                          err_msg=name)
            out = codecs.unfuse_payload(buf, p)
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(p)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_single_leaf_skips_kernel(self, pallas_backend):
        p = {"raw": jnp.arange(6, dtype=jnp.bfloat16)}
        buf = codecs.fuse_payload(p)
        assert buf.size == 12


# ---------------------------------------------------------------------------
# unpack_payload: exact key-set dispatch, every registered codec
# ---------------------------------------------------------------------------

class TestUnpackDispatch:
    @pytest.mark.parametrize("name", codecs.registered_codecs())
    def test_every_codec_roundtrips(self, name):
        x = jax.random.normal(jax.random.PRNGKey(5), (4, 256))
        p = codecs.get_codec(name).pack(x, 0.25)
        got = codecs.unpack_payload(p, x.shape, jnp.float32)
        want = codecs.get_codec(name).unpack(p, x.shape, jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_q8_tile_payload_dispatches(self, pallas_backend):
        # the per-tile Pallas q8 format {codes, tile_meta} must dispatch on
        # its own key set, not ride on "codes" probing first.
        x = jax.random.normal(jax.random.PRNGKey(6), (16, 256))
        p = codecs.get_codec("q8").pack(x)
        assert set(p) == {"codes", "tile_meta"}
        got = codecs.unpack_payload(p, x.shape, jnp.float32)
        err = np.abs(np.asarray(got) - np.asarray(x))
        assert err.max() < float(x.max() - x.min()) / 255 + 1e-5

    def test_unknown_keyset_raises(self):
        with pytest.raises(ValueError, match="no registered codec"):
            codecs.unpack_payload({"bogus": jnp.zeros(3)}, (1, 3))
        # a SUBSET of a known key set must not silently dispatch either
        with pytest.raises(ValueError, match="no registered codec"):
            codecs.unpack_payload({"codes": jnp.zeros((1, 4), jnp.uint8)},
                                  (1, 4))

    def test_keyset_collision_rejected(self):
        class Dup(codecs.NoneCodec):
            name = "dup"
        with pytest.raises(ValueError, match="already registered"):
            codecs.register_codec(Dup())
        assert "dup" in codecs._REGISTRY   # name slot written before check
        del codecs._REGISTRY["dup"]


# ---------------------------------------------------------------------------
# DP decode+sum: deterministic rank-ordered fold, ulp-close to the loop
# ---------------------------------------------------------------------------

GRADS_LIKE = {"w": jnp.zeros((4, 33)), "b": jnp.zeros((7,)),
              "v": jnp.zeros((2, 64))}


def _mesh(dp):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:dp]).reshape(dp, 1)
    return Mesh(devs, ("data", "stages"))


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >= 2 devices")
class TestFusedDpDecodeSum:
    @pytest.mark.parametrize("codec", ["q8", "q4"])
    @pytest.mark.parametrize("feedback", ["none", "ef", "ef21"])
    def test_matches_reference_loop(self, codec, feedback):
        from repro.transport.collectives import (init_dp_state,
                                                 make_grad_all_reduce)
        dp = min(jax.device_count(), 4)
        mesh = _mesh(dp)
        g_dp = jax.tree.map(
            lambda a: jax.random.normal(jax.random.PRNGKey(7),
                                        (dp, *a.shape)), GRADS_LIKE)
        outs = {}
        prev = C.KERNEL_BACKEND
        try:
            for backend in ("jnp", "pallas"):
                C.KERNEL_BACKEND = backend
                red = make_grad_all_reduce(mesh, "data", codec,
                                           feedback=feedback)
                state = init_dp_state(GRADS_LIKE, dp, feedback)
                r, _ = red(g_dp, state)
                outs[backend] = jax.tree.map(np.asarray, r)
            # deterministic: the fused kernel twice -> bitwise equal
            C.KERNEL_BACKEND = "pallas"
            red = make_grad_all_reduce(mesh, "data", codec,
                                       feedback=feedback)
            state = init_dp_state(GRADS_LIKE, dp, feedback)
            r2, _ = red(g_dp, state)
        finally:
            C.KERNEL_BACKEND = prev
        for k in GRADS_LIKE:
            a, b = outs["jnp"][k], outs["pallas"][k]
            # static rank-ordered fold: only FMA contraction may differ,
            # bounded by 1 ulp per dequant across the dp-term sum.
            tol = dp * 1.2e-7 * max(np.abs(a).max(), 1.0)
            np.testing.assert_allclose(a, b, rtol=0, atol=tol)
            np.testing.assert_array_equal(outs["pallas"][k],
                                          np.asarray(r2[k]))

    @pytest.mark.parametrize("codec", ["q8", "q4"])
    def test_plans_engage_for_dp_leaves(self, codec, pallas_backend):
        from repro.kernels.dp_reduce import build_decode_plans
        from repro.transport.collectives import grad_payload_structs
        structs = grad_payload_structs(GRADS_LIKE, codec)
        plans = build_decode_plans(
            structs, [a.shape for a in jax.tree.leaves(GRADS_LIKE)])
        assert plans is not None
        kinds = {p.kind for p in plans}
        assert kinds == {codec}
        # odd leaf (7,): q4 codes are (n+1)//2 bytes
        ns = sorted(p.n for p in plans)
        assert ns == [7, 128, 132]

    def test_plans_reject_unsupported(self):
        from repro.kernels.dp_reduce import build_decode_plans
        from repro.transport.collectives import grad_payload_structs
        for codec in ("none", "topk"):
            structs = grad_payload_structs(GRADS_LIKE, codec)
            assert build_decode_plans(
                structs,
                [a.shape for a in jax.tree.leaves(GRADS_LIKE)]) is None

    def test_decode_sum_kernel_direct(self, pallas_backend):
        """Kernel vs hand loop on manually packed hop buffers, incl. odd
        leaf, for every rank of the ring: each rank holds the buffers in
        its own hop order and must return the source-ordered sum."""
        from repro.kernels.dp_reduce import (build_decode_plans,
                                             decode_sum_fused)
        from repro.transport.collectives import (pack_grad_leaf,
                                                 unpack_grad_leaf)
        codec = codecs.get_codec("q4")
        dp = 3
        leaves = [jax.random.normal(jax.random.PRNGKey(i), (5, 33))
                  for i in range(dp)]
        payloads = [[pack_grad_leaf(codec, a)] for a in leaves]
        bufs = [codecs.fuse_payload(p) for p in payloads]
        struct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), payloads[0])
        plans = build_decode_plans(struct, [(5, 33)])
        assert plans is not None
        want = None
        for p in payloads:                   # source-rank order
            m = unpack_grad_leaf(codec, p[0], (5, 33))
            want = m if want is None else want + m
        got = [decode_sum_fused([bufs[(r - h) % dp] for h in range(dp)],
                                plans, jnp.int32(r))[0].reshape(5, 33)
               for r in range(dp)]
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   rtol=0, atol=dp * 1.2e-7 * 10)
        for g in got[1:]:                    # same bits on every rank
            np.testing.assert_array_equal(np.asarray(g), np.asarray(got[0]))
