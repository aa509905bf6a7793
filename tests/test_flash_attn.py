"""The blocked causal attention kernel (kernels/flash_attn.py) against the
dense path it replaces, in interpret mode, and the route that picks it.

Shapes are small (S = 256, two 128-blocks a side) so each case takes
seconds.  Inputs are bf16, as in training; the kernel accumulates QK^T in
f32 where the dense einsum rounds the logits to bf16 first, so the two
agree to bf16 rounding, not bitwise.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.attention as A
from repro.kernels.flash_attn import flash_attention, flash_block
from repro.models.common import apply_rope, rope_cos_sin, rope_rotate
from repro.obs import trace

S = 256


def _qkv(key, b, h, kv, hd):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, S, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, S, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, S, kv, hd), jnp.bfloat16)
    return q, k, v


def _dense(q, k, v):
    """The dense causal path of ``attn_train`` (``_sdpa``)."""
    mask = A._causal_mask(S, None, jnp.arange(S))
    return A._sdpa(q, k, v, mask, None)


def _kernel(q, k, v):
    """The kernel on the dense path's ``(B, S, H, hd)`` layout."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    qk = q.reshape(b, s, kv, h // kv, hd).transpose(0, 2, 3, 1, 4)
    qk = (qk.astype(jnp.float32) / np.sqrt(hd)).astype(q.dtype)
    out = flash_attention(qk, k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), interpret=True)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("groups", [1, 3])
def test_kernel_matches_dense(groups, hd):
    kv = 2
    q, k, v = _qkv(jax.random.PRNGKey(groups * 1000 + hd), 2, kv * groups,
                   kv, hd)
    out = jax.jit(_kernel)(q, k, v)
    ref = jax.jit(_dense)(q, k, v)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    err = np.max(np.abs(np.asarray(out, np.float32)
                        - np.asarray(ref, np.float32)))
    assert err < 0.04, err
    assert _rel(out, ref) < 0.01

    cot = jax.random.normal(jax.random.PRNGKey(7), ref.shape, jnp.float32)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * cot)

    g_kernel = jax.jit(jax.grad(functools.partial(loss, _kernel),
                                (0, 1, 2)))(q, k, v)
    g_dense = jax.jit(jax.grad(functools.partial(loss, _dense),
                               (0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", g_kernel, g_dense):
        assert a.shape == b.shape, name
        assert _rel(a, b) < 0.02, (name, _rel(a, b))


@pytest.mark.parametrize("hd", [64, 128])
def test_rope_rotate_is_apply_rope(hd):
    """The kernel route's RoPE (half swap on the MXU, heads-major layout)
    rotates exactly as the dense route's ``apply_rope``."""
    x = jax.random.normal(jax.random.PRNGKey(hd), (2, S, 3, hd),
                          jnp.bfloat16)
    want = apply_rope(x, jnp.arange(S)[None], 1e4)
    cos, sin = rope_cos_sin(jnp.arange(S), hd, 1e4)
    got = rope_rotate(x.transpose(0, 2, 1, 3), cos, sin)
    np.testing.assert_array_equal(np.asarray(got.transpose(0, 2, 1, 3)),
                                  np.asarray(want))
    scaled = rope_rotate(x.transpose(0, 2, 1, 3), cos, sin, 0.125)
    np.testing.assert_array_equal(
        np.asarray(scaled.transpose(0, 2, 1, 3)),
        np.asarray((want.astype(jnp.float32) * 0.125).astype(want.dtype)))


@pytest.mark.parametrize("pos_embed", ["rope", "abs"])
def test_attn_train_splash_route_matches_dense(monkeypatch, pos_embed):
    """The whole layer on the kernel's route (projections into its layout,
    RoPE with the folded scale, output projection) against the dense
    route, on the same weights."""
    d, h, kv, hd = 256, 6, 2, 64
    params = A.attn_init(jax.random.PRNGKey(0), d, h, kv, hd)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, d), jnp.bfloat16)
    kw = dict(num_heads=h, num_kv_heads=kv, head_dim=hd, pos_embed=pos_embed)

    def run(p, x):
        return A.attn_train(p, x, **kw)

    def loss(p, x):
        return jnp.sum(run(p, x).astype(jnp.float32) ** 2)

    ref = jax.jit(run)(params, x)
    g_ref = jax.jit(jax.grad(loss))(params, x)
    # steer the route to the kernel while tracing; it still interprets
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    assert A.attn_route(S, hd, None, None, None, None) == "splash"
    out = jax.jit(run)(params, x)
    g_out = jax.jit(jax.grad(loss))(params, x)
    assert _rel(out, ref) < 0.01
    for name in ("wq", "wk", "wv", "wo"):
        assert _rel(g_out[name], g_ref[name]) < 0.02, name


@pytest.mark.parametrize("case, args", [
    ("window", dict(window=128)),
    ("softcap", dict(attn_softcap=50.0)),
    ("pad_mask", dict(pad_mask=jnp.ones((2, S), bool))),
    ("positions", dict(positions=jnp.arange(S))),
    ("not a block multiple", dict(s=200)),
    ("shorter than a block", dict(s=64)),
    ("head width not measured", dict(hd=32)),
    ("plain causal", dict()),
])
@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_attn_route(monkeypatch, case, args, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    full = dict(s=S, hd=64, window=None, attn_softcap=None, pad_mask=None,
                positions=None)
    full.update(args)
    want = "splash" if case == "plain causal" and backend == "tpu" else "dense"
    assert A.attn_route(**full) == want


@pytest.mark.parametrize("s, hd, block", [
    (256, 64, 256), (1024, 64, 1024), (4096, 128, 1024), (1536, 64, 512),
    (384, 128, 128), (200, 64, None), (64, 64, None), (1024, 32, None),
    (1024, 96, None)])
def test_flash_block_tiles_the_sequence(s, hd, block):
    assert flash_block(s, hd) == block


def test_route_instant_recorded_when_tracing():
    d, h, kv, hd = 128, 4, 2, 32
    params = A.attn_init(jax.random.PRNGKey(0), d, h, kv, hd)
    x = jnp.zeros((1, 16, d), jnp.bfloat16)
    tracer = trace.enable()
    try:
        jax.eval_shape(functools.partial(
            A.attn_train, num_heads=h, num_kv_heads=kv, head_dim=hd,
            window=8), params, x)
        events = [e for e in tracer.drain() if e.name == "attn.route"]
    finally:
        trace.disable()
    assert len(events) == 1
    assert events[0].ph == "i"
    assert events[0].args == {"route": "dense", "s": 16, "hd": hd, "g": 2}
    # off: nothing is recorded and nothing raises
    jax.eval_shape(functools.partial(
        A.attn_train, num_heads=h, num_kv_heads=kv, head_dim=hd), params, x)
    assert trace.get_tracer() is None
