"""Real shard_map pipeline: packed payloads + pipelined forward vs
sequential reference (core/pipeline.py, the beyond-paper path).

Needs >1 host device: spawned in a subprocess with
--xla_force_host_platform_device_count=4 so the main pytest process keeps
seeing exactly one device (DESIGN rule).  Payload packing itself is
single-device and tested in-process.
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

from repro.core.pipeline import pack_payload, unpack_payload, wire_bytes


class TestPayloadPacking:
    def _roundtrip(self, x, scheme, k=0.25):
        p = pack_payload(x, scheme, k)
        y = unpack_payload(p, x.shape, jnp.float32)
        return p, np.asarray(y)

    def test_none_exact_bf16(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 8))
        p, y = self._roundtrip(x, "none")
        np.testing.assert_allclose(y, np.asarray(x.astype(jnp.bfloat16),
                                                 dtype=np.float32))
        assert wire_bytes(p) == x.size * 2

    def test_q8_tight(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 96))
        p, y = self._roundtrip(x, "q8")
        span = float(x.max() - x.min())
        assert np.abs(y - np.asarray(x)).max() <= span / 255 + 1e-6

    def test_q4_pack_halves_bytes(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 128))
        p8 = pack_payload(x, "q8")
        p4 = pack_payload(x, "q4")
        assert p4["codes4"].size == p8["codes"].size // 2
        y = unpack_payload(p4, x.shape, jnp.float32)
        span = float(x.max() - x.min())
        assert np.abs(np.asarray(y) - np.asarray(x)).max() <= span / 15 + 1e-6

    def test_topk_scatter_matches_dense_topk(self):
        from repro.core.compressors import topk_compress
        x = jax.random.normal(jax.random.PRNGKey(3), (3, 64))
        p = pack_payload(x, "topk", 0.25)
        y = unpack_payload(p, x.shape, jnp.float32)
        dense = topk_compress(x, 0.25)
        np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                                   rtol=1e-2, atol=1e-2)

    @given(st.sampled_from(["none", "q8", "q4"]),
           st.integers(1, 5), st.integers(1, 4))
    @settings(max_examples=20, deadline=None)
    def test_shapes_roundtrip_property(self, scheme, b, blocks):
        n = 128 * blocks
        x = jax.random.normal(jax.random.PRNGKey(b * 7 + blocks), (b, n))
        p, y = self._roundtrip(x, scheme)
        assert y.shape == x.shape
        assert np.isfinite(y).all()

    def test_wire_bytes_ordering(self):
        """q4 < q8 < none; topk(10%) < none (bf16 values + int32 idx)."""
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 1024))
        b = {s: wire_bytes(pack_payload(x, s, 0.10))
             for s in ("none", "q8", "q4", "topk")}
        assert b["q4"] < b["q8"] < b["none"]
        assert b["topk"] < b["none"]


PIPE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.pipeline import pipeline_forward
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    B, D = 8, 64
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, D), jnp.float32)
    k1, k2 = jax.random.split(key)
    params = {"w1": jax.random.normal(k1, (4, D, 2 * D)) * 0.1,
              "w2": jax.random.normal(k2, (4, 2 * D, D)) * 0.1}
    stage_fn = lambda p, h: h + jnp.tanh(h @ p["w1"]) @ p["w2"]
    ref = x
    for s in range(4):
        ref = stage_fn(jax.tree.map(lambda a: a[s], params), ref)
    out = pipeline_forward(stage_fn, params, x, mesh, "stage", scheme="none")
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 0.05, f"pipeline vs sequential err {err}"
    out8 = pipeline_forward(stage_fn, params, x, mesh, "stage", scheme="q8")
    err8 = float(jnp.max(jnp.abs(out8 - ref)) / jnp.max(jnp.abs(ref)))
    assert err8 < 0.2, f"q8 pipeline rel err {err8}"
    print("PIPE_OK", err, err8)
""")


@pytest.mark.slow
def test_pipeline_matches_sequential_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", PIPE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PIPE_OK" in r.stdout
