"""Pallas TPU kernel: fused min-max k-bit quantize->dequantize.

The boundary-compression hot path.  A naive jnp implementation makes three
HBM round-trips (min/max reduce, quantize, dequantize); this kernel does one:
each (bm, bn) VMEM tile computes its own min/max on the VPU, quantizes and
dequantizes in-register, and writes the result once.

TPU adaptation vs the paper (DESIGN.md §4): scales are PER-TILE rather than
per-tensor — strictly more accurate at equal wire cost (one fp32 pair per
tile), and it removes the global reduction dependency so tiles pipeline
freely through the MXU/VPU-adjacent VMEM.

Tile shapes are (8k, 128m)-aligned.  Validated in interpret mode on CPU
against kernels/ref.py; TPU is the deployment target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _qdq_kernel(x_ref, o_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    xmin = jnp.min(x)
    xmax = jnp.max(x)
    span = xmax - xmin
    scale = jnp.where(span > 0, span / levels, 1.0)
    codes = jnp.clip(jnp.round((x - xmin) / scale), 0.0, float(levels))
    o_ref[...] = (codes * scale + xmin).astype(o_ref.dtype)


def _quantize_kernel(x_ref, codes_ref, meta_ref, *, levels: int):
    """Wire-format variant: uint8 codes + per-tile (min, scale) pair.

    The (gm, 2 gn) meta array stays resident in VMEM for the whole grid
    (a (1, 2) block per tile is not a legal Mosaic block): each tile
    writes its two lanes through a mask.  Mosaic has no float->uint8
    cast, so the codes go through int32."""
    i, j = pl.program_id(0), pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    xmin = jnp.min(x)
    xmax = jnp.max(x)
    span = xmax - xmin
    scale = jnp.where(span > 0, span / levels, 1.0)
    codes = jnp.clip(jnp.round((x - xmin) / scale), 0.0, float(levels))
    codes_ref[...] = codes.astype(jnp.int32).astype(jnp.uint8)

    @pl.when((i == 0) & (j == 0))
    def _():
        meta_ref[...] = jnp.zeros(meta_ref.shape, meta_ref.dtype)

    rows = jax.lax.broadcasted_iota(jnp.int32, meta_ref.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, meta_ref.shape, 1)
    here = rows == i
    meta_ref[...] = jnp.where(
        here & (cols == 2 * j), xmin,
        jnp.where(here & (cols == 2 * j + 1), scale, meta_ref[...]))


def quant_dequant(x: jnp.ndarray, bits: int, *, block=(256, 256),
                  interpret: bool | None = None) -> jnp.ndarray:
    """x: (M, N), N % 128 == 0.  Returns C(x) with per-tile scales."""
    assert x.ndim == 2, x.shape
    m, n = x.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (x.shape, (bm, bn))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        functools.partial(_qdq_kernel, levels=(1 << bits) - 1),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(x)


def quantize_wire(x: jnp.ndarray, bits: int, *, block=(256, 256),
                  interpret: bool | None = None):
    """Returns (codes uint8 (M,N), meta fp32 (tiles_m, 2*tiles_n)) — the
    actual bytes a pipeline boundary sends (see core/pipeline.py)."""
    assert x.ndim == 2 and bits <= 8
    m, n = x.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (x.shape, (bm, bn))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    gm, gn = m // bm, n // bn
    codes, meta = pl.pallas_call(
        functools.partial(_quantize_kernel, levels=(1 << bits) - 1),
        out_shape=(jax.ShapeDtypeStruct((m, n), jnp.uint8),
                   jax.ShapeDtypeStruct((gm, 2 * gn), jnp.float32)),
        grid=(gm, gn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=(pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                   pl.BlockSpec((gm, 2 * gn), lambda i, j: (0, 0))),
        interpret=interpret,
        name="quantize_wire",
    )(x)
    return codes, meta


def dequantize_wire(codes, meta, dtype=jnp.float32, *, block=(256, 256)):
    """jnp inverse of quantize_wire (receiver side)."""
    m, n = codes.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    gm, gn = m // bm, n // bn
    mins = meta[:, 0::2]
    scales = meta[:, 1::2]
    c = codes.reshape(gm, bm, gn, bn).astype(dtype)
    out = (c * scales[:, None, :, None].astype(dtype)
           + mins[:, None, :, None].astype(dtype))
    return out.reshape(m, n)
