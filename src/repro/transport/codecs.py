"""Wire codecs: the byte formats a stage boundary actually sends.

One codec = one wire scheme.  ``pack`` maps a boundary tensor ``(B, ...)``
to a payload pytree of arrays (static structure, static shapes — required
inside ``lax.scan`` / ``ppermute``); ``unpack`` inverts it given the
original shape.  Both the simulated boundary (core/boundary.py) and the
real ``ppermute`` pipeline (transport/pipeline.py) consume THIS registry,
so bytes-on-wire accounting and compression semantics cannot drift apart.

Registered schemes:

  * ``none`` — raw bf16                            (2    bytes/elem)
  * ``q8``   — uint8 codes + per-tensor min/scale  (1    byte/elem)
  * ``q4``   — two 4-bit codes packed per uint8    (0.5  byte/elem)
  * ``topk`` — (bf16 values, uint16/int32 indices) (k*(2+idx) bytes/elem)

Quantization uses PER-TENSOR min/max scales so that
``codec.roundtrip(x) == quantize_dequantize(x, bits)`` exactly — the
simulated boundary's C(x) and the real wire round-trip are bit-identical
(tested in tests/test_transport.py).  TopK indices are ``uint16`` whenever
the flattened per-example feature dim fits in 16 bits, ``int32`` otherwise.

On TPU the codec hot path routes through the fused Pallas wire kernels
(see the README "Kernels" section): ``q8`` via kernels/quantize.py
(per-tile scales) when the flattened shape tiles into 128-lane blocks,
``q4`` via kernels/pack4.py and TopK via kernels/topk_select.py (both
per-tensor, byte- resp. set-identical to the jnp formats, at any shape),
and multi-leaf payload framing via kernels/framing.py.  Off the TPU, and
for a q8 shape with no 8-row tiling or a hop buffer over the framing
kernel's VMEM guard, the pure-jnp path is used (``wire_route`` says
which).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.compressors import (Compressor, dequantize_kbit,
                                    quantize_kbit, topk_scatter,
                                    topk_values_indices)

# Index dtype threshold: a flattened feature dim of up to 2**16 entries has
# indices 0..65535, exactly the uint16 range.
_U16_MAX_N = 1 << 16


def _flat_n(shape) -> int:
    n = 1
    for s in shape[1:]:
        n *= s
    return n


class WireCodec:
    """Base class: a named wire format with a bytes-per-element cost model.

    ``pack(x, k_frac)``   : (B, ...) tensor -> payload dict (static shapes).
    ``unpack(payload, shape, dtype)`` : payload -> (B, ...) tensor.
    ``wire_bytes_per_elem(n, elem_bytes, k_frac)`` : cost model, excluding
    the per-tensor scale overhead (O(1) bytes).
    """

    name: str = "?"

    def payload_keysets(self) -> Tuple[Tuple[str, ...], ...]:
        """The exact key sets this codec's ``pack`` can emit — registered
        alongside the codec so ``unpack_payload`` dispatches on the full
        key SET, not on whichever single key happens to probe first."""
        raise NotImplementedError

    def pack(self, x: jnp.ndarray, k_frac: float = 1.0) -> dict:
        raise NotImplementedError

    def unpack(self, payload: dict, shape, dtype=jnp.bfloat16) -> jnp.ndarray:
        raise NotImplementedError

    def wire_bytes_per_elem(self, n: int, elem_bytes: int = 2,
                            k_frac: float = 1.0) -> float:
        raise NotImplementedError

    def roundtrip(self, x: jnp.ndarray, k_frac: float = 1.0,
                  dtype=None) -> jnp.ndarray:
        """pack -> unpack: the dense C(x) equivalent of this wire format."""
        return self.unpack(self.pack(x, k_frac), x.shape,
                           dtype or x.dtype)


class NoneCodec(WireCodec):
    """Raw bf16 — the uncompressed baseline wire format."""

    name = "none"

    def payload_keysets(self):
        return (("raw",),)

    def pack(self, x, k_frac: float = 1.0):
        return {"raw": x.astype(jnp.bfloat16)}

    def unpack(self, payload, shape, dtype=jnp.bfloat16):
        return payload["raw"].astype(dtype)

    def wire_bytes_per_elem(self, n, elem_bytes: int = 2,
                            k_frac: float = 1.0) -> float:
        return float(elem_bytes)


def _pallas_tiling(flat_shape) -> Optional[Tuple[int, int]]:
    """(bm, bn) for the tiled Pallas wire kernels, or None when no tiling
    fits — the feature dim is not a 128-multiple, or the row block (largest
    power-of-two divisor of m, capped at 256) would under-fill the native
    8-sublane tile.  See kernels/tiling.py."""
    from repro.kernels.tiling import wire_tiling
    return wire_tiling(flat_shape)


class QuantCodec(WireCodec):
    """Uniform k-bit min-max quantization; 4-bit packs two codes per byte.

    Per-tensor scales (paper Sec. 2.2) on the jnp path; on TPU the 8-bit
    variant uses the fused Pallas wire kernels with per-tile scales
    (kernels/quantize.py — strictly more accurate at the same wire cost).
    """

    def __init__(self, bits: int):
        assert bits in (4, 8), bits
        self.bits = bits
        self.name = f"q{bits}"

    def payload_keysets(self):
        if self.bits == 4:
            return (("codes4", "min", "scale"),)
        return (("codes", "min", "scale"),      # per-tensor jnp format
                ("codes", "tile_meta"))         # per-tile Pallas format

    def pack(self, x, k_frac: float = 1.0):
        b = x.shape[0]
        flat = x.reshape(b, -1)
        pallas = wire_route(self.name, x.shape) == "pallas"
        if self.bits == 8 and pallas:
            from repro.kernels.quantize import quantize_wire
            codes, meta = quantize_wire(flat.astype(jnp.float32), 8,
                                        block=_pallas_tiling(flat.shape))
            return {"codes": codes, "tile_meta": meta}
        if self.bits == 4 and pallas:
            from repro.kernels.pack4 import pack4_wire
            packed, mn, sc = pack4_wire(flat.astype(jnp.float32))
            return {"codes4": packed, "min": mn, "scale": sc}
        codes, mn, sc = quantize_kbit(flat.astype(jnp.float32), self.bits,
                                      axis=None)
        if self.bits == 4:
            n = flat.shape[1]
            if n % 2:                       # odd feature dim: pad one code
                codes = jnp.pad(codes, ((0, 0), (0, 1)))
            even = codes[:, 0::2]
            odd = codes[:, 1::2]
            packed = (even | (odd << 4)).astype(jnp.uint8)
            return {"codes4": packed, "min": mn, "scale": sc}
        return {"codes": codes, "min": mn, "scale": sc}

    def unpack(self, payload, shape, dtype=jnp.bfloat16):
        b = shape[0]
        n = _flat_n(shape)
        if "codes4" in payload:
            packed = payload["codes4"]
            if _use_pallas_wire():
                from repro.kernels.pack4 import unpack4_wire
                flat = unpack4_wire(packed, payload["min"],
                                    payload["scale"], n)
                return flat.reshape(shape).astype(dtype)
            even = packed & 0xF
            odd = packed >> 4
            codes = jnp.stack([even, odd], axis=-1).reshape(b, -1)[:, :n]
            flat = dequantize_kbit(codes, payload["min"], payload["scale"])
            return flat.reshape(shape).astype(dtype)
        if "tile_meta" in payload:
            from repro.kernels.quantize import dequantize_wire
            codes, meta = payload["codes"], payload["tile_meta"]
            gm, gn = meta.shape[0], meta.shape[1] // 2
            block = (codes.shape[0] // gm, codes.shape[1] // gn)
            flat = dequantize_wire(codes, meta, jnp.float32, block=block)
            return flat.reshape(shape).astype(dtype)
        flat = dequantize_kbit(payload["codes"], payload["min"],
                               payload["scale"])
        return flat.reshape(shape).astype(dtype)

    def wire_bytes_per_elem(self, n, elem_bytes: int = 2,
                            k_frac: float = 1.0) -> float:
        return self.bits / 8.0


class TopKCodec(WireCodec):
    """(values, indices) of the largest-|.| k_frac entries per example.

    Values ride as bf16; indices are uint16 when the flattened feature dim
    fits in 16 bits (n <= 65536), int32 otherwise — for the paper's typical
    boundary (seq x d_model bf16, 10% kept) that is 0.1*(2+2)=0.4 bytes per
    original element instead of 0.6.
    """

    name = "topk"

    def payload_keysets(self):
        return (("idx", "vals"),)

    def pack(self, x, k_frac: float = 0.1):
        b = x.shape[0]
        flat = x.reshape(b, -1)
        n = flat.shape[1]
        if wire_route(self.name, x.shape) == "pallas":
            from repro.kernels.topk_select import topk_select_wire
            k = max(1, int(round(k_frac * n)))   # same k as the jnp path
            vals, idx = topk_select_wire(flat, k)
        else:
            vals, idx = topk_values_indices(flat, k_frac)
        if n <= _U16_MAX_N:
            idx = idx.astype(jnp.uint16)
        return {"vals": vals.astype(jnp.bfloat16), "idx": idx}

    def unpack(self, payload, shape, dtype=jnp.bfloat16):
        idx = payload["idx"].astype(jnp.int32)
        return topk_scatter(payload["vals"].astype(jnp.float32), idx,
                            shape, jnp.float32).astype(dtype)

    def wire_bytes_per_elem(self, n, elem_bytes: int = 2,
                            k_frac: float = 0.1) -> float:
        idx_bytes = 2 if n <= _U16_MAX_N else 4
        return k_frac * (elem_bytes + idx_bytes)


def _use_pallas_wire() -> bool:
    from repro.core.compressors import _use_pallas
    return _use_pallas()


def wire_route(name: str, shape) -> str:
    """Which path packs a ``shape`` tensor with codec ``name`` on this
    backend: ``"pallas"`` (a wire kernel) or ``"jnp"``.  Only q8 has a
    shape guard: it needs an 8-row tiling (so the per-request ``(1, n)``
    serve payloads stay jnp); ``none`` is a cast and never a kernel."""
    if name == "none" or not _use_pallas_wire():
        return "jnp"
    if name == "q8" and _pallas_tiling((shape[0], _flat_n(shape))) is None:
        return "jnp"
    return "pallas"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, WireCodec] = {}

# frozenset(payload keys) -> codec name: the unpack_payload dispatch table,
# built at registration from each codec's declared payload_keysets().
_PAYLOAD_KEYSETS: Dict[frozenset, str] = {}


def register_codec(codec: WireCodec) -> WireCodec:
    """Add a codec to the registry (future schemes plug in here)."""
    _REGISTRY[codec.name] = codec
    for keys in codec.payload_keysets():
        ks = frozenset(keys)
        owner = _PAYLOAD_KEYSETS.get(ks)
        if owner is not None and owner != codec.name:
            raise ValueError(f"payload key set {sorted(ks)} already "
                             f"registered to codec {owner!r}")
        _PAYLOAD_KEYSETS[ks] = codec.name
    return codec


def get_codec(name: str) -> WireCodec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown wire scheme {name!r}; "
                         f"registered: {sorted(_REGISTRY)}") from None


def registered_codecs() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_codec(NoneCodec())
register_codec(QuantCodec(8))
register_codec(QuantCodec(4))
register_codec(TopKCodec())


def codec_for(comp: Compressor) -> WireCodec:
    """The wire codec realizing a :class:`Compressor` on the network.

    ``codec_for(c).roundtrip(x)`` equals ``c(x)`` on the jnp backend —
    the invariant that makes the simulated boundary wire-faithful.
    """
    if comp.kind == "none":
        return get_codec("none")
    if comp.kind == "quant":
        if comp.bits not in (4, 8):
            raise ValueError(f"no wire codec for {comp.bits}-bit quantization"
                             " (registered: q4, q8)")
        return get_codec(f"q{comp.bits}")
    if comp.kind == "topk":
        return get_codec("topk")
    raise ValueError(f"no wire codec for compressor kind {comp.kind!r}")


# ---------------------------------------------------------------------------
# Functional wrappers (the original core/pipeline.py API)
# ---------------------------------------------------------------------------

def pack_payload(x: jnp.ndarray, scheme: str, k_frac: float = 0.1) -> dict:
    """x: (B, ...) stage output -> wire pytree (static shapes)."""
    return get_codec(scheme).pack(x, k_frac)


def unpack_payload(payload: dict, shape, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Inverse of :func:`pack_payload`: dispatches on the payload's EXACT
    key set, registered per codec via ``payload_keysets()``."""
    name = _PAYLOAD_KEYSETS.get(frozenset(payload))
    if name is None:
        known = sorted(sorted(ks) for ks in _PAYLOAD_KEYSETS)
        raise ValueError(f"payload keys {sorted(payload)} match no "
                         f"registered codec wire format; known: {known}")
    return get_codec(name).unpack(payload, shape, dtype)


def wire_bytes(payload) -> int:
    """Actual bytes-on-wire of a packed payload."""
    return sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(payload))


# ---------------------------------------------------------------------------
# Payload fusion: one contiguous byte buffer per hop
# ---------------------------------------------------------------------------
# A packed payload is a pytree (q8: codes + min + scale; EF-mixed: two full
# payloads), and ``ppermute`` lowers one collective-permute PER LEAF.  On a
# latency-bound interconnect each launch costs the collective's fixed
# overhead, so the fused schedules bitcast every leaf to uint8, concatenate,
# and send ONE buffer per direction per tick — byte-identical on the wire
# (same total payload bytes, pure bitcasts) but a single collective launch.
#
# When the Pallas wire kernels are on, the concatenate (and the slicing on
# the receive side) routes through the one-pass framing kernel
# (kernels/framing.py) — same bytes, one kernel instead of a concat chain.


def _leaf_nbytes(s) -> int:
    nb = jnp.dtype(s.dtype).itemsize
    for dim in s.shape:
        nb *= dim
    return nb


def _bytes_to_leaf(seg: jnp.ndarray, s):
    """Flat uint8 segment -> array of the leaf's shape/dtype (the inverse
    of the per-leaf bitcast in :func:`fuse_payload`)."""
    itemsize = jnp.dtype(s.dtype).itemsize
    if itemsize == 1:
        a = seg.reshape(s.shape)
        return a.astype(s.dtype) if s.dtype == jnp.bool_ else \
            jax.lax.bitcast_convert_type(a, s.dtype)
    return jax.lax.bitcast_convert_type(
        seg.reshape(*s.shape, itemsize), s.dtype)


def _use_pallas_framing(total_bytes: int, n_parts: int) -> bool:
    if n_parts < 2 or not _use_pallas_wire():
        return False
    from repro.kernels.framing import FRAME_MAX_BYTES
    return 0 < total_bytes <= FRAME_MAX_BYTES


def fuse_payload(payload) -> jnp.ndarray:
    """Flatten a packed payload pytree into one contiguous uint8 vector."""
    parts = []
    for a in jax.tree.leaves(payload):
        b = (a.astype(jnp.uint8) if a.dtype == jnp.bool_
             else jax.lax.bitcast_convert_type(a, jnp.uint8))
        parts.append(b.reshape(-1))
    if not parts:
        return jnp.zeros((0,), jnp.uint8)
    if len(parts) == 1:
        return parts[0]
    if _use_pallas_framing(sum(p.size for p in parts), len(parts)):
        from repro.kernels.framing import frame_parts
        return frame_parts(parts)
    return jnp.concatenate(parts)


def unfuse_payload(buf: jnp.ndarray, payload_struct):
    """Inverse of :func:`fuse_payload` given the payload's shape/dtype
    structure (``jax.eval_shape`` of the pack, or the payload itself)."""
    leaves, treedef = jax.tree.flatten(payload_struct)
    sizes = [_leaf_nbytes(s) for s in leaves]
    if _use_pallas_framing(sum(sizes), len(leaves)):
        from repro.kernels.framing import unframe_parts
        segs = unframe_parts(buf, sizes)
    else:
        segs, off = [], 0
        for nb in sizes:
            segs.append(buf[off:off + nb])
            off += nb
    out = [_bytes_to_leaf(seg, s) for seg, s in zip(segs, leaves)]
    return jax.tree.unflatten(treedef, out)
