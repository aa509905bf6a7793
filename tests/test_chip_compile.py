"""Compile rehearsals for a described TPU v5e: the wire kernels, the
causal attention kernel and the full-width gpt2-small train step, at the
shapes the main path gives them.

Nothing runs: each test compiles for a chip that is described, not
attached, so the TPU compiler (Mosaic for the kernels) refuses here what
it would refuse on the chip — unaligned blocks, casts it has no lowering
for, blocks over the VMEM limit.  Interpret-mode tests cannot see any of
that.  The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.  The persistent compilation cache is off around
these compiles (an entry written for a described chip cannot be read
back here).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tiling import wire_tiling

# the training boundary of gpt2-small: 8 examples x 1024 tokens x 768
BOUNDARY = (8, 1024 * 768)
# one microbatch of it on the 2-stage x 2-tensor mesh: 4 rows, half the
# sequence
MICROBATCH = (4, 512 * 768)
# one served token's cut tensor
TOKEN = (1, 768)
SHAPES = [BOUNDARY, MICROBATCH, TOKEN]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Compile for the described chip; the kernels must be in it."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("shape", [BOUNDARY, (8, 65536)])
def test_quantize_wire_compiles(one_chip, shape):
    from repro.kernels.quantize import quantize_wire
    block = wire_tiling(shape)
    _compile(lambda x: quantize_wire(x, 8, block=block, interpret=False),
             _spec(one_chip, shape))


@pytest.mark.parametrize("shape", SHAPES)
def test_pack4_wire_compiles(one_chip, shape):
    from repro.kernels.pack4 import pack4_wire
    _compile(lambda x: pack4_wire(x, interpret=False),
             _spec(one_chip, shape))


@pytest.mark.parametrize("shape", SHAPES)
def test_unpack4_wire_compiles(one_chip, shape):
    from repro.kernels.pack4 import unpack4_wire
    m, n = shape
    _compile(lambda p, mn, sc: unpack4_wire(p, mn, sc, n, interpret=False),
             _spec(one_chip, (m, (n + 1) // 2), jnp.uint8),
             _spec(one_chip, ()), _spec(one_chip, ()))


@pytest.mark.parametrize("shape", SHAPES)
def test_topk_threshold_compiles(one_chip, shape):
    from repro.kernels.topk_select import topk_threshold
    k = round(0.1 * shape[1])
    _compile(lambda x: topk_threshold(x, k, interpret=False),
             _spec(one_chip, shape))


def test_framing_compiles(one_chip):
    # a q4 boundary payload: packed codes + the f32 min and scale
    from repro.kernels.framing import frame_parts, unframe_parts
    sizes = [BOUNDARY[0] * BOUNDARY[1] // 2, 4, 4]
    parts = [_spec(one_chip, (nb,), jnp.uint8) for nb in sizes]
    _compile(lambda *ps: frame_parts(list(ps), interpret=False), *parts)
    _compile(lambda b: unframe_parts(b, sizes, interpret=False),
             _spec(one_chip, (sum(sizes),), jnp.uint8))


@pytest.mark.parametrize("codec", ["q8", "q4"])
def test_decode_sum_fused_compiles(one_chip, codec):
    # the LayerNorm leaves of gpt2-small's layer stack, reduced over 2
    # data-parallel replicas
    import repro.core.compressors as C
    from repro.kernels.dp_reduce import (build_decode_plans, decode_fits,
                                         decode_sum_fused)
    from repro.transport.codecs import get_codec
    shapes = [(12, 768)] * 4
    prev = C.KERNEL_BACKEND
    C.KERNEL_BACKEND = "jnp"
    try:
        structs = [jax.eval_shape(lambda: get_codec(codec).pack(
            jnp.zeros((1, 12 * 768), jnp.float32))) for _ in shapes]
    finally:
        C.KERNEL_BACKEND = prev
    plans = build_decode_plans(structs, shapes)
    assert plans is not None and decode_fits(plans, 2)
    nbytes = plans[-1].meta_off + 8
    _compile(lambda a, b, r: decode_sum_fused([a, b], plans, r,
                                              interpret=False),
             _spec(one_chip, (nbytes,), jnp.uint8),
             _spec(one_chip, (nbytes,), jnp.uint8),
             _spec(one_chip, (), jnp.int32))


def _train_step_args(sharding, cfg, opt, policy, transport, batch):
    """Shapes of ``(params, opt_state, bstates, batch, ids)`` for a
    gpt2-small train step at 1024 tokens, placed by ``sharding``."""
    from repro.launch.train import init_bstates
    from repro.models import transformer
    from repro.optim.optimizers import init_opt_state
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    state = (params, jax.eval_shape(lambda p: init_opt_state(opt, p), params),
             jax.eval_shape(lambda: init_bstates(cfg, policy, transport,
                                                 seq=1024, batch=batch)))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        state)
    tokens = jax.ShapeDtypeStruct((batch, 1024), jnp.int32,
                                  sharding=sharding)
    ids = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sharding)
    return (*state, {"tokens": tokens}, ids)


def _assert_splash(compiled):
    """Causal attention ran through the kernel, both ways: its forward,
    and its backward (one kernel for dq, dk and dv)."""
    text = compiled.as_text()
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv"):
        assert kernel in text, kernel


def _fits_v5e(compiled):
    mem = compiled.memory_analysis()
    return mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_gpt2_small_train_step_compiles(one_chip, monkeypatch):
    """The simulated q4q8 train step at full width (12 layers, d=768,
    vocab 50257), batch 8 x 1024 tokens, with its boundary kernels."""
    from repro.configs.registry import get
    from repro.launch.train import POLICIES, adamw_config
    from repro.train.steps import make_lm_train_step
    # the program picks its kernels from the backend: steer it to the TPU
    # branches while tracing (interpret mode would hide Mosaic)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get("gpt2-small")
    policy = POLICIES["q4q8"]()
    opt = adamw_config(1e-3, 3)
    step = make_lm_train_step(cfg, policy, opt, remat=True, donate=False)
    compiled = step.lower(*_train_step_args(
        one_chip, cfg, opt, policy, "simulated", 8)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_splash(compiled)
    assert _fits_v5e(compiled)


def test_gpt2_small_mesh_step_compiles(topo, monkeypatch):
    """The 2-stage x 2-tensor step with q8 on both wires, batch 16 (two
    8-row microbatches), over the four described chips: the stage wire's
    kernels must compile inside the pipeline and each chip's share fit."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.configs.registry import get
    from repro.core.parallel import spec_from_cli
    from repro.core.policy import NO_POLICY
    from repro.launch.train import adamw_config
    from repro.train.steps import _resolve_parallel, make_lm_train_step
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get("gpt2-small")
    opt = adamw_config(1e-3, 3)
    # Mesh() types its axes Auto, like launch/mesh.make_mesh
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 2, 2),
                ("data", "stage", "tensor"))
    spec = spec_from_cli("stage=2,tensor=2", "stage=q8,tensor=q8")
    _, policy, transport = _resolve_parallel("test", spec, NO_POLICY,
                                             "simulated", {})
    step = make_lm_train_step(cfg, NO_POLICY, opt, remat=True,
                              donate=False, parallel=spec, mesh=mesh)
    compiled = step.lower(*_train_step_args(
        NamedSharding(mesh, PartitionSpec()), cfg, opt, policy, transport,
        16)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_splash(compiled)
    assert _fits_v5e(compiled)


def test_starcoder2_attention_compiles(one_chip, monkeypatch):
    """One StarCoder2-7B attention layer (d 4608, 36 query heads over 4
    KV heads of 128) at 1 x 4096 tokens, forward and backward, on the
    kernel's route."""
    from repro.models import attention as A
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, h, kv, hd = 4608, 36, 4, 128
    kw = dict(num_heads=h, num_kv_heads=kv, head_dim=hd)
    assert A.attn_route(4096, hd, None, None, None, None) == "splash"
    params = jax.eval_shape(lambda: A.attn_init(jax.random.PRNGKey(0), d,
                                                h, kv, hd))
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          params)

    def loss(p, x):
        return jnp.sum(A.attn_train(p, x, **kw).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(
        params, _spec(one_chip, (1, 4096, d), jnp.bfloat16)).compile()
    _assert_splash(compiled)
    assert _fits_v5e(compiled)


def test_starcoder2_8l_mesh_step_compiles(topo, monkeypatch):
    """StarCoder2-7B at its published widths, 8 layers, on the 2-stage x
    2-tensor mesh with q8 on both wires: batch 16 x 1024 as two 8-row
    GPipe microbatches, donated state placed by
    ``launch.mesh.train_state_shardings``.  Each chip's share must fit its
    16 GiB, the state must come back in the layout it went in (so the
    donation takes), and the splash and q8 wire kernels must be in the
    program."""
    import dataclasses
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.configs.registry import get
    from repro.core.parallel import spec_from_cli
    from repro.core.policy import NO_POLICY
    from repro.launch.mesh import train_state_shardings
    from repro.launch.train import adamw_config
    from repro.models import transformer
    from repro.optim.optimizers import init_opt_state
    from repro.train.steps import make_lm_train_step
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get("starcoder2-7b"), num_layers=8)
    opt = adamw_config(1e-3, 3)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 2, 2),
                ("data", "stage", "tensor"))
    spec = spec_from_cli("stage=2,tensor=2", "stage=q8,tensor=q8")
    step = make_lm_train_step(cfg, NO_POLICY, opt, remat=True, donate=True,
                              parallel=spec, mesh=mesh,
                              pipeline_microbatches=2)
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    state = (params, jax.eval_shape(lambda p: init_opt_state(opt, p),
                                    params))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, train_state_shardings(state, mesh))
    rep = NamedSharding(mesh, PartitionSpec())
    compiled = step.lower(
        *state, [], {"tokens": jax.ShapeDtypeStruct((16, 1024), jnp.int32,
                                                    sharding=rep)},
        jax.ShapeDtypeStruct((16,), jnp.int32, sharding=rep)).compile()
    _assert_splash(compiled)
    assert "quantize_wire" in compiled.as_text()
    assert _fits_v5e(compiled)
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    went_in = [a.sharding for a in jax.tree.leaves(state)]
    came_out = jax.tree.leaves(compiled.output_shardings[:2])
    assert [s.spec for s in came_out] == [s.spec for s in went_in]
