"""Pallas TPU kernel: fused receive-side decode+sum for the DP ring.

After the ``ppermute`` ring (transport/collectives.py) every replica holds
``hops`` — the fused uint8 buffers of all ``dp`` source ranks, in HOP order
(hop ``h`` came from source ``(r - h) % dp``).  The jnp path then runs, per
hop and per parameter leaf, an unfuse slice + bitcast + dequantize, and a
source-ordered add: O(dp * leaves) kernel launches and a dense f32 HBM
round-trip per step, on the receive path of every ring hop.  The kernel
here does the whole thing in one launch: for each leaf it walks the ``dp``
byte segments at their static offsets, decodes the uint8 codes in-register
(q8 bytes or q4 nibble pairs, the same ``codes * scale + min`` dequant as
``dequantize_kbit``), picks each source's decoded value by a select on the
rank and accumulates in a STATIC source-rank-ordered fold.  The fold
association is fixed and every replica executes the identical program, so
all replicas still compute a bitwise-identical reduced gradient — the DP
acceptance invariant, asserted in tests/test_codec_kernels.py.  Against
the unfused XLA reference loop the dequant may differ by at most 1 ulp
where the compiler contracts the multiply-add into an FMA (a
strictly-more-precise rounding; the tests pin this bound).

The per-source per-leaf (min, scale) f32 scalars are extracted from the
buffer bytes by XLA bitcasts beforehand (Mosaic has no size-changing
bitcast) and ride into the kernel as one ``(dp, 2 * leaves)`` operand.
``build_decode_plans`` validates the payload layout and returns ``None``
whenever this kernel does not apply (raw/TopK/per-tile payloads, empty
leaves, VMEM overflow) — the caller then keeps the reference loop.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# hop buffers + meta + f32 accumulators and one leaf decoded by hop, all
# resident at once.
DECODE_MAX_BYTES = 4 * 1024 * 1024

_Q8_KEYS = frozenset(("codes", "min", "scale"))
_Q4_KEYS = frozenset(("codes4", "min", "scale"))


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """Static byte layout of one leaf's payload inside the fused buffer:
    ``kind`` q8/q4, codes at ``[off, off + nbytes)``, the f32 (min, scale)
    pair at ``[meta_off, meta_off + 8)``, dense feature count ``n``."""
    kind: str
    off: int
    nbytes: int
    meta_off: int
    n: int


def build_decode_plans(structs, leaf_shapes) -> Optional[List[LeafPlan]]:
    """Byte-layout plans for a list of per-leaf payload structs (the
    ``eval_shape`` dicts ``fuse_payload`` flattens), or ``None`` when the
    fused kernel does not apply.  Offsets follow ``jax.tree.leaves`` order
    — per-dict keys sorted, so codes always precede min/scale."""
    if len(structs) != len(leaf_shapes):
        return None
    plans, off = [], 0
    for s, shape in zip(structs, leaf_shapes):
        if not isinstance(s, dict):
            return None                      # raw passthrough (codec none)
        keys = frozenset(s)
        if keys == _Q8_KEYS:
            kind = "q8"
            codes = s["codes"]
        elif keys == _Q4_KEYS:
            kind = "q4"
            codes = s["codes4"]
        else:
            return None                      # topk / per-tile q8
        n = 1
        for d in shape:
            n *= d
        nbytes = 1
        for d in codes.shape:
            nbytes *= d
        if (n == 0 or codes.dtype != jnp.uint8
                or s["min"].shape != () or s["scale"].shape != ()
                or jnp.dtype(s["min"].dtype).itemsize != 4
                or jnp.dtype(s["scale"].dtype).itemsize != 4):
            return None
        expect = (n + 1) // 2 if kind == "q4" else n
        if nbytes != expect:
            return None
        plans.append(LeafPlan(kind, off, nbytes, off + nbytes, n))
        off += nbytes + 8
    return plans


def extract_meta(hops: Sequence[jnp.ndarray], plans: Sequence[LeafPlan]):
    """``dp`` (nbytes,) uint8 hop buffers -> (dp, 2 * leaves) f32 of
    per-hop (min, scale) pairs, bitcast straight from the payload bytes."""
    rows = []
    for b in hops:
        cols = []
        for p in plans:
            for o in (p.meta_off, p.meta_off + 4):
                cols.append(jax.lax.bitcast_convert_type(b[o:o + 4],
                                                         jnp.float32))
        rows.append(jnp.stack(cols))
    return jnp.stack(rows)


def _decode_sum_kernel(order_ref, meta_ref, *refs,
                       plans: Sequence[LeafPlan], dp: int):
    """One output per q8 leaf; two (even and odd nibble planes) per q4
    leaf — Mosaic has no lane interleave, so the planes are interleaved
    once, after the fold, in XLA.  uint8 widens through int32 (Mosaic has
    no uint8->float cast).  ``order_ref[0, s]`` is the hop that holds
    source ``s``; the select compares it as a vector (a lane mask)."""
    hop_refs, outs = refs[:dp], iter(refs[dp:])
    for li, p in enumerate(plans):
        by_hop = []
        for h in range(dp):
            seg = hop_refs[h][:, p.off:p.off + p.nbytes].astype(jnp.int32)
            mn = meta_ref[h, 2 * li]
            sc = meta_ref[h, 2 * li + 1]
            planes = ((seg,) if p.kind == "q8" else (seg & 0xF, seg >> 4))
            by_hop.append([c.astype(jnp.float32) * sc + mn for c in planes])
        accs = None
        for s in range(dp):                  # static rank-ordered fold
            which = jnp.full(by_hop[0][0].shape, order_ref[0, s], jnp.int32)
            ds = by_hop[0]
            for h in range(1, dp):
                ds = [jnp.where(which == h, d, a)
                      for a, d in zip(ds, by_hop[h])]
            accs = ds if accs is None else [a + d for a, d in zip(accs, ds)]
        for a in accs:
            next(outs)[...] = a


def decode_fits(plans: Sequence[LeafPlan], dp: int,
                budget: int = DECODE_MAX_BYTES) -> bool:
    nbytes = plans[-1].meta_off + 8 if plans else 0
    dense = sum(p.n for p in plans) * 4
    by_hop = dp * max((p.n for p in plans), default=0) * 4
    return dp * nbytes + dense + by_hop + dp * len(plans) * 8 <= budget


def decode_sum_fused(hops: Sequence[jnp.ndarray], plans: Sequence[LeafPlan],
                     rank, *,
                     interpret: bool | None = None) -> List[jnp.ndarray]:
    """hops: the ``dp`` (nbytes,) uint8 buffers of the ring in hop order
    (:func:`repro.transport.collectives.ring_exchange`: hop ``h`` holds
    source ``(rank - h) % dp``); ``rank``: this replica's index on the
    ring (traced).  Returns one (1, n) float32 rank-summed dense gradient
    per leaf plan — the same static source-rank-ordered association as
    the unfuse->dequantize->add reference loop (identical on every
    replica; <= 1 ulp of FMA rounding vs the unfused loop)."""
    dp = len(hops)
    assert all(b.ndim == 1 and b.dtype == jnp.uint8 for b in hops), [
        (b.shape, b.dtype) for b in hops]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    meta = extract_meta(hops, plans)
    order = ((rank - jnp.arange(dp, dtype=jnp.int32)) % dp).reshape(1, dp)
    out = iter(pl.pallas_call(
        functools.partial(_decode_sum_kernel, plans=tuple(plans), dp=dp),
        out_shape=[jax.ShapeDtypeStruct((1, p.nbytes), jnp.float32)
                   for p in plans for _ in range(1 if p.kind == "q8" else 2)],
        interpret=interpret,
    )(order, meta, *[b.reshape(1, -1) for b in hops]))
    dense = []
    for p in plans:
        if p.kind == "q8":
            dense.append(next(out))
        else:
            even, odd = next(out), next(out)
            dense.append(jnp.stack([even, odd], axis=-1)
                         .reshape(1, -1)[:, :p.n])
    return dense
