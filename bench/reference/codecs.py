"""Plain float32 codecs at the stage cuts, as the program applies them.

On the TPU the simulated boundary's compressors run the Pallas kernels
(``repro.core.compressors.Compressor`` -> ``repro.kernels.ops``) on the
boundary tensor flattened per example to (B, S*d):

* quantization: min-max k-bit quantize->dequantize with one (min, scale)
  pair per (bm, bn) tile (kernels/quantize.py ``quant_dequant``);
* TopK: per row and per (bm, bn) tile, keep |x| >= a threshold found by 24
  halvings of [0, max|x|] so that about ceil(k_frac * bn) entries stay
  (kernels/topk_mask.py ``topk_block``).

The tile is (largest power-of-two divisor of B capped at 256, largest of
2048/1024/512/256/128 dividing S*d) (kernels/tiling.py).  These are
copies of the arithmetic of kernels/ref.py, written again here so that
the reference imports nothing of the program.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

LANE_BLOCKS = (2048, 1024, 512, 256, 128)
TOPK_ITERS = 24


def tile(m: int, n: int):
    bm = min(256, m & -m)
    bn = next((c for c in LANE_BLOCKS if n % c == 0), None)
    if bn is None:
        return m, n
    return bm, bn


def _tiles(x, bm, bn):
    m, n = x.shape
    return x.reshape(m // bm, bm, n // bn, bn)


def quant_dequant(x, bits: int):
    """(B, ...) -> per-tile min-max k-bit quantize->dequantize."""
    m = x.shape[0]
    flat = x.reshape(m, -1)
    bm, bn = tile(*flat.shape)
    t = _tiles(flat, bm, bn)
    levels = float((1 << bits) - 1)
    lo = t.min(axis=(1, 3), keepdims=True)
    hi = t.max(axis=(1, 3), keepdims=True)
    span = hi - lo
    scale = jnp.where(span > 0, span / levels, 1.0)
    codes = jnp.clip(jnp.round((t - lo) / scale), 0.0, levels)
    return (codes * scale + lo).reshape(x.shape)


def topk_block(x, k_frac: float):
    """(B, ...) -> per-row, per-tile TopK by threshold bisection."""
    m = x.shape[0]
    flat = x.reshape(m, -1)
    bm, bn = tile(*flat.shape)
    k = float(max(1, int(math.ceil(k_frac * bn))))
    t = _tiles(flat, bm, bn)
    mag = jnp.abs(t)
    hi = mag.max(axis=3, keepdims=True)
    lo = jnp.zeros_like(hi)
    for _ in range(TOPK_ITERS):
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.float32), axis=3, keepdims=True)
        gt = cnt > k
        lo = jnp.where(gt, mid, lo)
        hi = jnp.where(gt, hi, mid)
    return jnp.where(mag >= lo, t, 0.0).reshape(x.shape)


def apply(spec, x):
    """``spec``: ["none"] | ["quant", bits] | ["topk", k_frac]."""
    kind = spec[0]
    if kind == "none":
        return x
    if kind == "quant":
        return quant_dequant(x, int(spec[1]))
    if kind == "topk":
        return topk_block(x, float(spec[1]))
    raise ValueError(f"unknown codec {spec!r}")

