"""Pallas TPU kernel: one-pass payload framing for fused wire hops.

``fuse_payload`` (transport/codecs.py) turns a packed payload pytree into
ONE contiguous uint8 buffer so each pipeline tick / DP ring hop costs a
single collective launch.  The jnp path builds that buffer with a
``concatenate`` over the bitcast leaves — XLA materializes every operand
and then copies the lot into a fresh buffer, an extra HBM round-trip on
every hop's send path.  The kernel here writes each leaf directly into its
static byte offset of the hop buffer in one pass (and the inverse slices
each leaf back out), so framing is one kernel instead of a concat chain.

The per-leaf dtype->uint8 bitcasts stay in XLA (they are layout metadata,
not data movement; Mosaic has no size-changing bitcast) — the kernel sees
only flat uint8 segments, so the framed buffer is BYTE-IDENTICAL to the
concat path by construction (asserted in tests/test_codec_kernels.py).
Dispatch lives in ``fuse_payload`` / ``unfuse_payload`` behind
``_use_pallas_wire()`` with a VMEM-residency guard; multi-leaf payloads
only (a single leaf needs no framing at all).
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The whole hop buffer is resident twice (segments + output).  A (1, n)
# uint8 row sits in VMEM padded to 4 sublanes, so the kernel needs about
# 8x the buffer's bytes: 32 MiB at the cap, over the 16 MiB default
# scoped limit of a v5e (which has 128 MiB of VMEM).
FRAME_MAX_BYTES = 4 * 1024 * 1024
FRAME_VMEM_LIMIT = 48 * 1024 * 1024


def _frame_kernel(*refs, sizes: Sequence[int]):
    o_ref = refs[-1]
    off = 0
    for r, nb in zip(refs[:-1], sizes):
        o_ref[:, off:off + nb] = r[...]
        off += nb


def _unframe_kernel(b_ref, *o_refs, sizes: Sequence[int]):
    off = 0
    for r, nb in zip(o_refs, sizes):
        r[...] = b_ref[:, off:off + nb]
        off += nb


def frame_parts(parts: List[jnp.ndarray], *,
                interpret: bool | None = None) -> jnp.ndarray:
    """Concatenate flat uint8 leaf segments into one hop buffer with a
    single Pallas kernel — byte-identical to ``jnp.concatenate(parts)``."""
    assert all(p.dtype == jnp.uint8 and p.ndim == 1 for p in parts), parts
    parts = [p for p in parts if p.size]
    sizes = tuple(int(p.size) for p in parts)
    total = sum(sizes)
    if len(parts) < 2:
        return parts[0] if parts else jnp.zeros((0,), jnp.uint8)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    buf = pl.pallas_call(
        functools.partial(_frame_kernel, sizes=sizes),
        out_shape=jax.ShapeDtypeStruct((1, total), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FRAME_VMEM_LIMIT),
        interpret=interpret,
    )(*[p.reshape(1, -1) for p in parts])
    return buf.reshape(-1)


def unframe_parts(buf: jnp.ndarray, sizes: Sequence[int], *,
                  interpret: bool | None = None) -> List[jnp.ndarray]:
    """Inverse of :func:`frame_parts`: slice the hop buffer back into flat
    uint8 segments of the given byte ``sizes`` (zero-size entries come back
    as empty arrays without touching the kernel)."""
    assert buf.dtype == jnp.uint8 and buf.ndim == 1, (buf.dtype, buf.shape)
    live = [nb for nb in sizes if nb]
    if len(live) < 2:
        out, off = [], 0
        for nb in sizes:
            out.append(buf[off:off + nb])
            off += nb
        return out
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    segs = pl.pallas_call(
        functools.partial(_unframe_kernel, sizes=live),
        out_shape=[jax.ShapeDtypeStruct((1, nb), jnp.uint8) for nb in live],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FRAME_VMEM_LIMIT),
        interpret=interpret,
    )(buf.reshape(1, -1))
    segs = iter(segs)
    return [next(segs).reshape(-1) if nb else jnp.zeros((0,), jnp.uint8)
            for nb in sizes]
