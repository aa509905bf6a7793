"""GQA attention: full / sliding-window / logit-softcap variants.

Three entry points per layer:
  * ``attn_train``   — full-sequence causal self-attention (training/prefill);
    plain causal attention on the TPU runs the blocked Pallas kernel
    (``kernels/flash_attn.py``), every other case the dense path
    (:func:`attn_route` decides)
  * ``attn_prefill`` — attn_train + returns the filled KV cache
  * ``attn_decode``  — one new token against a KV cache (full or ring buffer)

Cache layout: ``{"k": (B, C, KV, hd), "v": (B, C, KV, hd)}`` where C is the
full context for global layers and ``window`` for SWA layers (ring buffer —
this is what makes mixtral/gemma2 long_500k decode sub-quadratic in memory).
RoPE is applied at *write* time so ring slots never need re-rotation.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn import flash_attention, flash_block
from repro.models.common import (DTYPE, apply_rope, dense_init, rope_cos_sin,
                                 rope_rotate, softcap)
from repro.obs.trace import instant, scope
from repro.sharding.ctx import constrain


def attn_init(key, d: int, num_heads: int, num_kv_heads: int, head_dim: int,
              dtype=DTYPE):
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], d, num_heads * head_dim, dtype),
            "wk": dense_init(ks[1], d, num_kv_heads * head_dim, dtype),
            "wv": dense_init(ks[2], d, num_kv_heads * head_dim, dtype),
            "wo": dense_init(ks[3], num_heads * head_dim, d, dtype)}


def _project_qkv(params, x, num_heads, num_kv_heads, head_dim):
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, num_heads, head_dim)
    k = (x @ params["wk"]).reshape(b, s, num_kv_heads, head_dim)
    v = (x @ params["wv"]).reshape(b, s, num_kv_heads, head_dim)
    return q, k, v


def _sdpa_block(q, k, v, mask, cap: Optional[float]):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd); mask broadcast to (B,H,S,T)."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    groups = h // kv
    qg = q.reshape(b, s, kv, groups, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(hd))
    logits = softcap(logits, cap)
    logits = jnp.where(mask[:, None, None, :, :] if mask.ndim == 3
                       else mask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, hd)


def _qchunk(s: int) -> int:
    """Query-chunk size: bounds the materialized (S_chunk x T) logits so
    long-sequence training/prefill never holds an S x S tensor (flash-style;
    the python loop keeps HLO cost analysis exact, unlike a scan)."""
    if s <= 2048:
        return s
    return max(2048, s // 4)


def _sdpa(q, k, v, mask, cap: Optional[float]):
    s = q.shape[1]
    qc = _qchunk(s)
    if qc >= s:
        return _sdpa_block(q, k, v, mask, cap)
    outs = []
    for i in range(0, s, qc):
        mi = mask[:, i:i + qc] if mask.ndim == 3 else mask
        outs.append(_sdpa_block(q[:, i:i + qc], k, v, mi, cap))
    return jnp.concatenate(outs, axis=1)


def _causal_mask(s: int, window: Optional[int], positions) -> jnp.ndarray:
    """(1, S, S) bool mask; window==None => plain causal."""
    qp = positions[:, None]          # (S,1)
    kp = positions[None, :]          # (1,S)
    m = kp <= qp
    if window is not None:
        m &= kp > qp - window
    return m[None]


def attn_route(s: int, hd: int, window, attn_softcap, pad_mask,
               positions) -> str:
    """Which path :func:`attn_train` takes: ``"splash"`` (the blocked
    Pallas kernel) or ``"dense"`` (materialised scores, :func:`_sdpa`).
    The kernel covers plain causal attention over ``arange(s)`` on the
    TPU, at a length and head width it takes (``flash_block``); a window,
    a logit softcap, a pad mask, explicit positions or another backend
    stay dense."""
    if jax.default_backend() != "tpu":
        return "dense"
    if window is not None or attn_softcap is not None:
        return "dense"
    if pad_mask is not None or positions is not None:
        return "dense"
    return "dense" if flash_block(s, hd) is None else "splash"


def _attn_splash(params, x, *, num_heads, num_kv_heads, head_dim, pos_embed,
                 rope_theta):
    """:func:`attn_train` through the kernel.  The projections emit its
    ``(B, KV, G, S, hd)`` layout and the output projection reads it back,
    so no transpose of the heads stands alone; ``1/sqrt(hd)`` is folded
    into q in f32, with RoPE (``rope_rotate``, whole heads in the minor
    dimension) where there is RoPE."""
    b, s, d = x.shape
    g = num_heads // num_kv_heads
    q = jnp.einsum("bsd,dkgh->bkgsh", x,
                   params["wq"].reshape(d, num_kv_heads, g, head_dim))
    k = jnp.einsum("bsd,dkh->bksh", x,
                   params["wk"].reshape(d, num_kv_heads, head_dim))
    v = jnp.einsum("bsd,dkh->bksh", x,
                   params["wv"].reshape(d, num_kv_heads, head_dim))
    scale = 1.0 / head_dim ** 0.5
    if pos_embed == "rope":
        cos, sin = rope_cos_sin(jnp.arange(s), head_dim, rope_theta)
        q = rope_rotate(q, cos, sin, scale)
        k = rope_rotate(k, cos, sin)
    else:
        q = (q.astype(jnp.float32) * jnp.float32(scale)).astype(q.dtype)
    out = flash_attention(q, k, v)
    return jnp.einsum("bkgsh,kghe->bse", out,
                      params["wo"].reshape(num_kv_heads, g, head_dim, d))


@scope("attn")
def attn_train(params, x, *, num_heads, num_kv_heads, head_dim,
               pos_embed="rope", rope_theta=10_000.0, window=None,
               attn_softcap=None, positions=None, pad_mask=None):
    """``pad_mask``: optional (B, S) bool, True = real token.  Pad keys are
    masked out of every query's context (left-padded serving batches —
    RoPE logits depend only on position differences, so masking alone
    makes a padded prompt exactly equal to the same prompt unpadded)."""
    b, s, d = x.shape
    route = attn_route(s, head_dim, window, attn_softcap, pad_mask, positions)
    instant("attn.route", cat="attn", route=route, s=s, hd=head_dim,
            g=num_heads // num_kv_heads)
    if route == "splash":
        return _attn_splash(params, x, num_heads=num_heads,
                            num_kv_heads=num_kv_heads, head_dim=head_dim,
                            pos_embed=pos_embed, rope_theta=rope_theta)
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(params, x, num_heads, num_kv_heads, head_dim)
    if pos_embed == "rope":
        q = apply_rope(q, positions[None], rope_theta)
        k = apply_rope(k, positions[None], rope_theta)
    mask = _causal_mask(s, window, positions)
    if pad_mask is not None:
        mask = mask & pad_mask[:, None, :]          # (B, S, S)
    out = _sdpa(q, k, v, mask, attn_softcap)
    out = out.reshape(b, s, num_heads * head_dim)
    return out @ params["wo"]


def tp_local_heads(num_heads, num_kv_heads, tp):
    """Per-rank head counts for tp-way head-sharded attention."""
    if num_heads % tp or num_kv_heads % tp:
        raise ValueError(
            f"tensor parallelism shards attention heads: num_heads "
            f"{num_heads} and num_kv_heads {num_kv_heads} must both be "
            f"divisible by tp={tp}")
    return num_heads // tp, num_kv_heads // tp


@scope("attn")
def attn_train_tp(params, x_shard, tpc, *, num_heads, num_kv_heads,
                  head_dim, pos_embed="rope", rope_theta=10_000.0,
                  window=None, attn_softcap=None, buf=None):
    """Column/row-parallel :func:`attn_train` over a compressed tensor
    ring (transport/tp_collectives.py).

    ``params`` are the LOCAL shards — wq/wk/wv split on the head out-dim,
    wo on its head in-dim — and ``x_shard`` the sequence-sharded (normed)
    residual.  The in-gather crosses the compressed wire (``buf`` is this
    site's feedback buffer), attention runs on local heads over the FULL
    sequence (RoPE/causality are exact), and the partial ``wo`` output
    reduce-scatters back to the sequence shard.
    """
    lh, lkv = tp_local_heads(num_heads, num_kv_heads, tpc.tp)
    full, buf = tpc.gather_site(x_shard, buf)
    partial = attn_train(params, full, num_heads=lh, num_kv_heads=lkv,
                         head_dim=head_dim, pos_embed=pos_embed,
                         rope_theta=rope_theta, window=window,
                         attn_softcap=attn_softcap)
    return tpc.scatter(partial), buf


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_cache(batch: int, cache_len: int, num_kv_heads: int, head_dim: int,
               dtype=DTYPE):
    shape = (batch, cache_len, num_kv_heads, head_dim)
    k = constrain(jnp.zeros(shape, dtype), "batch", "seq", "model", None)
    v = constrain(jnp.zeros(shape, dtype), "batch", "seq", "model", None)
    return {"k": k, "v": v}


@scope("attn")
def attn_decode(params, x1, cache, pos, *, num_heads, num_kv_heads, head_dim,
                pos_embed="rope", rope_theta=10_000.0, window=None,
                attn_softcap=None, pad_len=None):
    """One-token decode.  x1: (B, 1, d); pos: scalar int32 (current index)
    or (B,) int32 per-slot indices (continuous-batching serve: each batch
    slot decodes its own request at its own position).

    ``window`` set => the cache is a ring buffer of length ``cache["k"].shape[1]
    == window`` and slots hold RoPE-rotated keys at their absolute positions.
    ``pad_len``: optional (B,) int32 — cache slots holding absolute
    positions < pad_len[b] are left-padding and masked out.
    """
    b = x1.shape[0]
    c = cache["k"].shape[1]
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    q, k, v = _project_qkv(params, x1, num_heads, num_kv_heads, head_dim)
    if pos_embed == "rope":
        posb = pos[:, None] if per_slot else jnp.full((1, 1), pos)
        q = apply_rope(q, posb, rope_theta)
        k = apply_rope(k, posb, rope_theta)
    slot = pos % c if window is not None else pos
    if per_slot:
        # batch-dependent slot index: scatter one row per example
        batch_ix = jnp.arange(b)
        ck = cache["k"].at[batch_ix, slot].set(
            k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[batch_ix, slot].set(
            v[:, 0].astype(cache["v"].dtype))
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    idx = jnp.arange(c)
    posc = pos[:, None] if per_slot else pos            # (B,1) | scalar
    slotc = slot[:, None] if per_slot else slot
    if window is None:
        valid = idx <= posc                             # absolute layout
        abs_pos = jnp.broadcast_to(idx, valid.shape) if per_slot else idx
    else:
        # ring layout: slot i holds absolute position p_i where
        # p_i = pos - ((slot - i) mod c); valid iff p_i > pos - window
        age = (slotc - idx) % c
        valid = age < jnp.minimum(posc + 1, c)
        abs_pos = posc - age
    if per_slot:
        mask = valid                                    # (B, C)
        if pad_len is not None:
            mask = mask & (abs_pos >= pad_len[:, None])
        mask = mask[:, None, None, None, :]             # (B,1,1,1,C)
    elif pad_len is None:
        mask = valid[None, None, None, :]               # (1,1,1,C) -> bcast
    else:
        # (B,1,1,1,C): batch must align with dim 0 of the (b,kv,g,s,t)
        # logits, not broadcast against kv heads
        mask = (valid[None] & (abs_pos[None] >= pad_len[:, None])
                )[:, None, None, None, :]
    out = _sdpa(q, ck, cv, mask, attn_softcap)
    out = out.reshape(b, 1, num_heads * head_dim)
    return out @ params["wo"], {"k": ck, "v": cv}


@scope("attn")
def attn_decode_span(params, x, cache, pos, *, num_heads, num_kv_heads,
                     head_dim, pos_embed="rope", rope_theta=10_000.0,
                     window=None, attn_softcap=None, pad_len=None,
                     page_map=None, valid_len=None):
    """Multi-token decode: ``x`` is (B, T, d) new tokens occupying absolute
    positions ``pos[b] + arange(T)``.  One program shape covers chunked
    prefill (B=1, T=chunk) and speculative verification (T=k+1); T=1
    reproduces :func:`attn_decode` bit-for-bit on the same cache contents.

    Cache forms:
      * slab  — ``cache["k"]: (B, C, KV, hd)`` (page_map None), the PR-4
        slot-indexed layout; ``pad_len`` masks left-padding as usual.
      * paged — ``cache["k"]: (N, P, KV, hd)`` (a page POOL) read/written
        through ``page_map: (B, n_pages) int32`` per-slot page indices;
        logical position t lives in physical page ``page_map[b, t // P]``
        at offset ``t % P``.  Unallocated logical pages map to the trash
        page 0 — never valid under the position mask.

    ``valid_len``: optional (B,) int32 — only the first valid_len[b] of the
    T tokens are real (a padded final prefill chunk).  Invalid positions'
    K/V are routed to the trash page (paged; the slab path requires full
    validity) and their queries produce garbage logits the caller ignores.

    Ring (sliding-window) caches are not supported: pages need absolute
    positions.
    """
    if window is not None:
        raise ValueError("attn_decode_span: sliding-window ring caches "
                         "are unsupported (absolute positions only)")
    b, t, _ = x.shape
    pos = jnp.asarray(pos)
    wpos = pos[:, None] + jnp.arange(t)                 # (B, T) abs positions
    q, k, v = _project_qkv(params, x, num_heads, num_kv_heads, head_dim)
    if pos_embed == "rope":
        q = apply_rope(q, wpos, rope_theta)
        k = apply_rope(k, wpos, rope_theta)
    if page_map is not None:
        p = cache["k"].shape[1]                         # page size
        phys = jnp.take_along_axis(page_map, wpos // p, axis=1)  # (B, T)
        if valid_len is not None:
            phys = jnp.where(jnp.arange(t)[None] < valid_len[:, None],
                             phys, 0)                   # pad -> trash page
        off = wpos % p
        ck = cache["k"].at[phys, off].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[phys, off].set(v.astype(cache["v"].dtype))
        vk = ck[page_map].reshape(b, -1, num_kv_heads, head_dim)
        vv = cv[page_map].reshape(b, -1, num_kv_heads, head_dim)
    else:
        batch_ix = jnp.arange(b)[:, None]
        ck = cache["k"].at[batch_ix, wpos].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[batch_ix, wpos].set(v.astype(cache["v"].dtype))
        vk, vv = ck, cv
    c = vk.shape[1]
    idx = jnp.arange(c)
    mask = idx[None, None, :] <= wpos[:, :, None]       # (B, T, C) causal
    if pad_len is not None:
        mask &= idx[None, None, :] >= pad_len[:, None, None]
    out = _sdpa(q, vk, vv, mask, attn_softcap)
    out = out.reshape(b, t, num_heads * head_dim)
    return out @ params["wo"], {"k": ck, "v": cv}


@scope("attn")
def attn_prefill(params, x, *, cache_len, num_heads, num_kv_heads, head_dim,
                 pos_embed="rope", rope_theta=10_000.0, window=None,
                 attn_softcap=None, pad_mask=None):
    """Full-sequence forward that also fills the cache (inference prefill).
    ``pad_mask``: optional (B, S) bool, True = real token (see attn_train)."""
    b, s, d = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(params, x, num_heads, num_kv_heads, head_dim)
    if pos_embed == "rope":
        q = apply_rope(q, positions[None], rope_theta)
        k = apply_rope(k, positions[None], rope_theta)
    mask = _causal_mask(s, window, positions)
    if pad_mask is not None:
        mask = mask & pad_mask[:, None, :]              # (B, S, S)
    out = _sdpa(q, k, v, mask, attn_softcap)
    out = out.reshape(b, s, num_heads * head_dim)
    ring = window is not None
    csize = cache_len if not ring else min(window, cache_len)
    cache = init_cache(b, csize, num_kv_heads, head_dim, k.dtype)
    c = min(csize, s)
    klast = k[:, s - c:].astype(cache["k"].dtype)
    vlast = v[:, s - c:].astype(cache["v"].dtype)
    if ring and c == csize and s % c:
        # ring semantics: abs position p lives at slot p % c
        klast = jnp.roll(klast, s % c, axis=1)
        vlast = jnp.roll(vlast, s % c, axis=1)
    ck = jax.lax.dynamic_update_slice(cache["k"], klast, (0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], vlast, (0, 0, 0, 0))
    return out @ params["wo"], {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, d: int, num_heads: int, head_dim: int, dtype=DTYPE):
    return attn_init(key, d, num_heads, num_heads, head_dim, dtype)


@scope("attn")
def cross_attn(params, x, memory, *, num_heads, head_dim):
    """x: (B,S,d) queries; memory: (B,T,d) encoder output (non-causal)."""
    b, s, _ = x.shape
    t = memory.shape[1]
    q = (x @ params["wq"]).reshape(b, s, num_heads, head_dim)
    k = (memory @ params["wk"]).reshape(b, t, num_heads, head_dim)
    v = (memory @ params["wv"]).reshape(b, t, num_heads, head_dim)
    mask = jnp.ones((1, 1, 1, t), bool)
    out = _sdpa(q, k, v, mask, None).reshape(b, s, num_heads * head_dim)
    return out @ params["wo"]
