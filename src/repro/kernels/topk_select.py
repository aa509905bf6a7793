"""Pallas TPU kernel: exact per-row TopK threshold, sort-free.

The TopK wire format (transport/codecs.py) sends the largest-|x| k entries
per example as (values, indices).  The jnp path pays for a full
``lax.top_k`` — an O(n log n) sort per row, hostile to the VPU and the
single expensive op on the TopK hot path.  The kernel here replaces the
sort with an EXACT threshold search: |x| is bitcast to int32 (the IEEE
ordering trick — non-negative floats compare identically to their bit
patterns), and 31 fixed bisection steps over the bit space find the
k-th-largest magnitude's exact bit pattern with nothing but vector
compares and per-row sum reductions.  A boundary row (seq x d_model
floats) does not fit VMEM, so the grid is (row blocks, bit, column
blocks): each bisection step streams the row through in (bm, bn) blocks,
sums the per-row count, and settles its bit after the last block.
Widths that are not a lane multiple are zero-padded: a zero magnitude
never counts, since every candidate bit pattern is >= 1.
Unlike the approximate magnitude bisection in kernels/topk_mask.py, the
bit-space search terminates at the EXACT k-th value, so the selected set
matches ``lax.top_k`` entry-for-entry.

The select/gather epilogue (tie resolution + index compaction) is a thin
cumsum + one scatter in XLA — O(n) streaming work Mosaic cannot express
(per-lane scatter), and exactly what XLA is good at.  Same on unpack: the
dense scatter stays on ``topk_scatter``.  The selected (values, indices)
SET equals the jnp path's; only the order differs — ascending index here
vs descending value from ``lax.top_k`` — with ties broken toward lower
indices in both, so the scattered dense tensor is bit-identical
(tests/test_codec_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import column_tiling, padded_width


_BITS = 31                         # magnitude bits below the sign


def _threshold_kernel(x_ref, t_ref, cnt_ref, *, k: int):
    b, c = pl.program_id(1), pl.program_id(2)
    last_c = pl.num_programs(2) - 1

    @pl.when((b == 0) & (c == 0))
    def _():
        t_ref[...] = jnp.zeros(t_ref.shape, t_ref.dtype)

    @pl.when(c == 0)
    def _():
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, cnt_ref.dtype)

    t = t_ref[...]                                      # (bm, 1) int32
    cand = t | jnp.left_shift(jnp.int32(1), _BITS - 1 - b)
    mag = jnp.abs(x_ref[...].astype(jnp.float32))       # (bm, bn)
    bits = jax.lax.bitcast_convert_type(mag, jnp.int32)
    cnt_ref[...] += jnp.sum((bits >= cand).astype(jnp.int32), axis=1,
                            keepdims=True)

    @pl.when(c == last_c)
    def _():
        t_ref[...] = jnp.where(cnt_ref[...] >= k, cand, t)


def topk_threshold(flat: jnp.ndarray, k: int, *,
                   interpret: bool | None = None) -> jnp.ndarray:
    """flat: (M, N).  Returns the EXACT k-th largest |x| per row, (M, 1)
    float32 — count(|x| >= thresh) >= k and count(|x| > thresh) < k."""
    assert flat.ndim == 2, flat.shape
    m, n = flat.shape
    assert 1 <= k <= n, (k, n)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    npad = padded_width(n, 128)
    if npad != n:
        flat = jnp.pad(flat, ((0, 0), (0, npad - n)))
    bm, bn = column_tiling(m, npad, max_lanes=32768,
                           bytes_per_elem=flat.dtype.itemsize)
    t, _ = pl.pallas_call(
        functools.partial(_threshold_kernel, k=k),
        out_shape=(jax.ShapeDtypeStruct((m, 1), jnp.int32),
                   jax.ShapeDtypeStruct((m, 1), jnp.int32)),
        grid=(m // bm, _BITS, npad // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, b, c: (i, c))],
        out_specs=(pl.BlockSpec((bm, 1), lambda i, b, c: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i, b, c: (i, 0))),
        interpret=interpret,
    )(flat)
    return jax.lax.bitcast_convert_type(t, jnp.float32)


def topk_select_wire(flat: jnp.ndarray, k: int, *,
                     interpret: bool | None = None):
    """(M, N) -> (values (M, k) flat.dtype, indices (M, k) int32).

    Pallas threshold + cumsum/scatter compaction.  Keeps exactly the
    ``lax.top_k`` set per row (entries above the exact k-th magnitude,
    plus threshold ties broken toward LOWER index — top_k's stable tie
    rule); indices come out ascending instead of value-sorted."""
    m, n = flat.shape
    thresh = topk_threshold(flat, k, interpret=interpret)
    mag = jnp.abs(flat.astype(jnp.float32))
    gt = mag > thresh
    eq = mag == thresh
    c_gt = jnp.sum(gt.astype(jnp.int32), axis=1, keepdims=True)
    tie_rank = jnp.cumsum(eq.astype(jnp.int32), axis=1)
    keep = gt | (eq & (tie_rank <= k - c_gt))           # exactly k per row
    slot = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1,
                     k)                                 # k == dropped
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, n), 1)
    idx = jnp.zeros((m, k), jnp.int32).at[rows, slot].set(
        cols, mode="drop", unique_indices=True)
    vals = jnp.take_along_axis(flat, idx, axis=1)
    return vals, idx
