"""Pallas TPU kernels: fused 4-bit quantize->scale->pack and the inverse.

The q4 wire format (transport/codecs.py) packs two 4-bit codes per uint8 —
byte j of a row is ``code[2j] | code[2j+1] << 4`` — with PER-TENSOR
min/scale and one zero pad code when the feature dim is odd.  The pure-jnp
path materializes the dense code tensor, the padded copy, the even/odd
strided slices and the shifted OR: five elementwise HBM round-trips on
exactly the tensor compression is meant to shrink.  The kernels here do
one each way over (bm, bn) column blocks: ``pack`` quantizes a block in
VMEM, pairs and packs it in-register and writes the half-width bytes
once; ``unpack`` splits nibbles, dequantizes and writes the dense block.

Mosaic has no lane interleave (no minor-dim reshape to pairs, no
lane-strided load) and no float<->uint8 cast.  So the pairing runs on the
MXU: each 256-lane chunk of codes times a constant (256, 128) matrix with
``P[2j, j] = 1`` and ``P[2j+1, j] = 16`` gives ``code[2j] + 16 code[2j+1]``
— exact, since codes (0..15) and the weights are exact in bf16 and the
sums (< 256) in the f32 accumulator — and unpacking multiplies the two
nibble planes by the transposed selections.  Casts go through int32.
Widths that are not a multiple of 256 are padded in XLA with the tensor
minimum (code 0 — the odd-n pad code) and the pad bytes sliced off.

Scales stay per-tensor (paper Sec. 2.2), so the packed bytes are
BIT-IDENTICAL to the jnp path: the global min/max runs as one XLA reduce
before the kernel (min/max are associative — the reduction shape cannot
change the result), and the kernel consumes the two scalars as (1, 1)
operands.  Bytes-on-wire never change.  The ``unpack`` dequant
(``codes * scale + min``) may differ from ``dequantize_kbit`` by at most
1 ulp where the compiler contracts the multiply-add into an FMA (a
strictly-more-precise rounding).  Parity — including odd feature dims —
is asserted in tests/test_codec_kernels.py; the wire dispatch lives in
``transport/codecs.py`` behind ``_use_pallas_wire()``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.tiling import column_tiling, padded_width

_LEVELS = 15.0
_CHUNK = 256                      # dense lanes per MXU pairing step


def _pair_matrices():
    """(pack (256, 128), unpack-even (128, 256), unpack-odd (128, 256))."""
    j = np.arange(_CHUNK // 2)
    pack = np.zeros((_CHUNK, _CHUNK // 2), np.float32)
    pack[2 * j, j] = 1.0
    pack[2 * j + 1, j] = 16.0
    even = np.zeros((_CHUNK // 2, _CHUNK), np.float32)
    even[j, 2 * j] = 1.0
    odd = np.zeros((_CHUNK // 2, _CHUNK), np.float32)
    odd[j, 2 * j + 1] = 1.0
    return (jnp.asarray(pack, jnp.bfloat16), jnp.asarray(even, jnp.bfloat16),
            jnp.asarray(odd, jnp.bfloat16))


def _pack4_kernel(x_ref, mn_ref, sc_ref, p_ref, o_ref):
    mn = mn_ref[0, 0]
    sc = sc_ref[0, 0]
    half = _CHUNK // 2
    for c in range(x_ref.shape[1] // _CHUNK):           # static unroll
        x = x_ref[:, c * _CHUNK:(c + 1) * _CHUNK].astype(jnp.float32)
        codes = jnp.clip(jnp.round((x - mn) / sc), 0.0, _LEVELS)
        packed = jax.lax.dot(codes.astype(jnp.bfloat16), p_ref[...],
                             preferred_element_type=jnp.float32)
        o_ref[:, c * half:(c + 1) * half] = (
            packed.astype(jnp.int32).astype(jnp.uint8))


def _unpack4_kernel(p_ref, mn_ref, sc_ref, e_ref, d_ref, o_ref):
    mn = mn_ref[0, 0]
    sc = sc_ref[0, 0]
    half = _CHUNK // 2
    for c in range(p_ref.shape[1] // half):             # static unroll
        p = p_ref[:, c * half:(c + 1) * half].astype(jnp.int32)
        even = (p & 0xF).astype(jnp.float32).astype(jnp.bfloat16)
        odd = (p >> 4).astype(jnp.float32).astype(jnp.bfloat16)
        codes = (jax.lax.dot(even, e_ref[...],
                             preferred_element_type=jnp.float32)
                 + jax.lax.dot(odd, d_ref[...],
                               preferred_element_type=jnp.float32))
        o_ref[:, c * _CHUNK:(c + 1) * _CHUNK] = (
            (codes * sc + mn).astype(o_ref.dtype))


def _minmax_scalars(flat):
    """Per-tensor (min, scale) — the same formula as quantize_kbit
    (axis=None), computed as one XLA reduce over the f32 input."""
    mn = jnp.min(flat)
    span = jnp.max(flat) - mn
    sc = jnp.where(span > 0, span / _LEVELS, jnp.ones_like(span))
    return mn, sc


def _const_spec(shape):
    return pl.BlockSpec(shape, lambda i, j: (0, 0))


def pack4_wire(flat: jnp.ndarray, *, interpret: bool | None = None):
    """flat: (M, N) float32.  Returns ``(packed uint8 (M, ceil(N/2)),
    min (), scale ())`` — bit-identical to the jnp q4 wire format."""
    assert flat.ndim == 2 and flat.dtype == jnp.float32, (
        flat.shape, flat.dtype)
    m, n = flat.shape
    h = (n + 1) // 2
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mn, sc = _minmax_scalars(flat)
    npad = padded_width(n, _CHUNK)
    if npad != n:                                       # pad code 0
        flat = jnp.pad(flat, ((0, 0), (0, npad - n)), constant_values=mn)
    bm, bn = column_tiling(m, npad, lane_multiple=_CHUNK)
    pair, _, _ = _pair_matrices()
    packed = pl.pallas_call(
        _pack4_kernel,
        out_shape=jax.ShapeDtypeStruct((m, npad // 2), jnp.uint8),
        grid=(m // bm, npad // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  _const_spec((1, 1)), _const_spec((1, 1)),
                  _const_spec(pair.shape)],
        out_specs=pl.BlockSpec((bm, bn // 2), lambda i, j: (i, j)),
        interpret=interpret,
    )(flat, mn.reshape(1, 1), sc.reshape(1, 1), pair)
    return packed[:, :h], mn, sc


def unpack4_wire(packed: jnp.ndarray, mn, sc, n: int, dtype=jnp.float32, *,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Inverse of :func:`pack4_wire`: (M, ceil(n/2)) uint8 -> (M, n)
    ``dtype`` — one fused unpack->dequant pass."""
    assert packed.ndim == 2 and packed.dtype == jnp.uint8, (
        packed.shape, packed.dtype)
    m, h = packed.shape
    assert h == (n + 1) // 2, (h, n)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    npad = padded_width(n, _CHUNK)
    if npad // 2 != h:
        packed = jnp.pad(packed, ((0, 0), (0, npad // 2 - h)))
    bm, bn = column_tiling(m, npad, lane_multiple=_CHUNK)
    _, even, odd = _pair_matrices()
    out = pl.pallas_call(
        _unpack4_kernel,
        out_shape=jax.ShapeDtypeStruct((m, npad), dtype),
        grid=(m // bm, npad // bn),
        in_specs=[pl.BlockSpec((bm, bn // 2), lambda i, j: (i, j)),
                  _const_spec((1, 1)), _const_spec((1, 1)),
                  _const_spec(even.shape), _const_spec(odd.shape)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(packed, jnp.asarray(mn, jnp.float32).reshape(1, 1),
      jnp.asarray(sc, jnp.float32).reshape(1, 1), even, odd)
    return out[:, :n]
