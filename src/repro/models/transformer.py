"""Decoder-only transformer stack with pipeline-stage compression boundaries.

The stack is organized as ``num_groups`` layer groups (a group is 1 layer for
uniform archs, 2 for gemma2 local/global or llama4 dense/moe interleave).
Groups are evenly split into ``policy.num_stages`` stages; between stages sits
a :mod:`repro.core.boundary` compression boundary — the paper's technique.
Within a stage we ``lax.scan`` over stacked layer params (keeps HLO small and
compile time bounded at 40+ layers), with ``jax.checkpoint`` per group.

Entry points:
  init_params(key, cfg)
  forward_train(params, batch, cfg, policy, bstates, ids) -> (logits, aux, new_fw)
  forward_eval(params, batch, cfg, policy, compress)      -> logits
  init_caches(cfg, batch, cache_len, dtype)
  prefill(params, batch, cfg, policy, cache_len, compress) -> (logits, caches)
  decode_step(params, token, caches, pos, cfg, policy, compress)
                                                           -> (logits, caches)
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.boundary import (boundary_apply, boundary_eval,
                                 empty_boundary_state,
                                 boundary_wire_eval,
                                 boundary_wire_eval_tokens)
from repro.core.policy import CompressionPolicy, NO_POLICY
from repro.models import blocks as B
from repro.models.common import DTYPE, embed_init, norm_apply, norm_init, softcap
from repro.models.config import ModelConfig
from repro.models.scan_config import scan_unroll
from repro.obs.trace import scope
from repro.sharding.ctx import constrain


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig, dtype=DTYPE):
    kinds = cfg.layer_kinds()
    g = cfg.num_groups
    ks = jax.random.split(key, len(kinds) + 3)
    layers = {}
    for i, kind in enumerate(kinds):
        gkeys = jax.random.split(ks[i], g)
        layers[f"b{i}"] = jax.vmap(
            lambda k: B.block_init(k, cfg, kind))(gkeys)
    params = {"embed": embed_init(ks[-1], cfg.vocab_size, cfg.d_model, dtype),
              "layers": layers,
              "final_norm": norm_init(cfg.d_model, cfg.norm)}
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(ks[-2], cfg.vocab_size, cfg.d_model,
                                       dtype)
    return params


def segment_bounds(num_groups: int, num_stages: int) -> List[Tuple[int, int]]:
    """Even split of groups into stages: [(g0, g1), ...]."""
    stages = min(num_stages, num_groups)
    per = num_groups / stages
    cuts = [int(round(per * s)) for s in range(stages + 1)]
    return [(cuts[i], cuts[i + 1]) for i in range(stages)
            if cuts[i + 1] > cuts[i]]


def _embed_lookup(embed, tokens):
    """Token embedding lookup.

    Under a mesh: one-hot matmul instead of gather — the gather's backward
    is a scatter-add that GSPMD can only partition by replicating the full
    fp32 (V, d) gradient (4.7 GB/device at vocab 256k); the one-hot dot and
    its transpose stay V-sharded and reduce with one psum (MaxText-style).
    """
    from repro.sharding.ctx import get_mesh
    if get_mesh() is None:
        return embed[tokens].astype(DTYPE)
    onehot = jax.nn.one_hot(tokens, embed.shape[0], dtype=DTYPE)
    # V over model here; activations re-shard to the S-over-model layout
    # at the caller.  S and V cannot both take the model axis in one einsum.
    onehot = constrain(onehot, "batch", None, "model")
    out = jnp.einsum("bsv,vd->bsd", onehot, embed.astype(DTYPE),
                     preferred_element_type=jnp.float32).astype(DTYPE)
    return constrain(out, "batch", None, None)


@scope("vocab")
def _embed_input(params, batch, cfg: ModelConfig):
    """batch: {"tokens": (B,S)} (+ "patch_embeds": (B,P,d) for vlm)."""
    tokens = batch["tokens"]
    x = _embed_lookup(params["embed"], tokens)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        p = batch["patch_embeds"].shape[1]
        x = jnp.concatenate(
            [batch["patch_embeds"].astype(x.dtype), x[:, p:]], axis=1)
    x = constrain(x, "batch", "model", None)
    return x


@scope("vocab")
def _lm_logits(params, x, cfg: ModelConfig):
    """Logits in bf16 (fp32 MXU accumulation, downcast fused into the
    matmul) — materializing fp32 (B,S,V) costs 4x the HBM of the weights
    at vocab 256k; the loss upcasts per-reduction instead (see lm_loss)."""
    x = constrain(x, "batch", None, None)     # release S from the model axis
    x = norm_apply(params["final_norm"], x, cfg.norm)
    head = params.get("lm_head", params["embed"])
    logits = jnp.einsum("bsd,vd->bsv", x.astype(DTYPE), head.astype(DTYPE),
                        preferred_element_type=jnp.float32).astype(DTYPE)
    logits = softcap(logits, cfg.final_softcap)
    return constrain(logits, "batch", None, "model")


def _slice_groups(tree, g0: int, g1: int):
    return jax.tree.map(lambda a: a[g0:g1], tree)


# ---------------------------------------------------------------------------
# Training forward (with boundary compression + feedback state threading)
# ---------------------------------------------------------------------------

def forward_hidden(params, batch, cfg: ModelConfig,
                   policy: CompressionPolicy = NO_POLICY,
                   bstates: Optional[list] = None,
                   ids: Optional[jnp.ndarray] = None,
                   remat: bool = True):
    """Returns (hidden_x, aux_loss, new_fw_buffers).

    ``bstates``: list of {"fw","bw"} per boundary (see core.boundary).  The
    bw buffers' updates come back as their cotangents — the train step takes
    grad w.r.t. them (see train/steps.py).
    """
    kinds = cfg.layer_kinds()
    x = _embed_input(params, batch, cfg)
    if ids is None:
        ids = jnp.zeros((x.shape[0],), jnp.int32)
    aux = jnp.float32(0.0)
    segs = segment_bounds(cfg.num_groups, policy.num_stages)
    new_fw = []

    def group_fn(x, gp):
        a = jnp.float32(0.0)
        for i, kind in enumerate(kinds):
            x, ai = B.block_train(gp[f"b{i}"], x, cfg, kind)
            a = a + ai
        # keep the scan carry (and the remat-saved residual) fully sharded:
        # batch over DP, SEQUENCE over TP (Megatron-SP layout: norms stay
        # collective-free; attention/mlp all-gather bf16 k/v as needed)
        x = constrain(x, "batch", "model", None)
        return x, a

    if remat:
        group_fn = jax.checkpoint(group_fn)

    for si, (g0, g1) in enumerate(segs):
        def scan_fn(carry, gp):
            x, a = carry
            x, ai = group_fn(x, gp)
            return (x, a + ai), None
        with jax.named_scope("layers"):
            (x, aux), _ = jax.lax.scan(
                scan_fn, (x, aux), _slice_groups(params["layers"], g0, g1),
                unroll=scan_unroll())
        if si < len(segs) - 1:
            bp = policy.at(si)
            st = (bstates[si] if bstates is not None
                  else empty_boundary_state(x.dtype))
            with jax.named_scope("codec"):
                x, nf = boundary_apply(bp, x, st["fw"], st["bw"], ids)
            new_fw.append(nf)
    return x, aux, new_fw


def forward_train(params, batch, cfg: ModelConfig,
                  policy: CompressionPolicy = NO_POLICY,
                  bstates: Optional[list] = None,
                  ids: Optional[jnp.ndarray] = None,
                  remat: bool = True):
    x, aux, new_fw = forward_hidden(params, batch, cfg, policy, bstates,
                                    ids, remat)
    return _lm_logits(params, x, cfg), aux, new_fw


def stage_stack_fn(cfg: ModelConfig, remat: bool = True):
    """``stage_fn(gp_stack, x) -> x`` applying a stacked slice of layer
    groups — the per-stage body for the REAL pipeline transport
    (transport/pipeline.py), with ``jax.checkpoint`` per group like
    :func:`forward_hidden`.  MoE aux losses are dropped on this path."""
    kinds = cfg.layer_kinds()

    def stage_fn(gp_stack, x):
        def scan_fn(x, gp):
            for i, kind in enumerate(kinds):
                x, _ = B.block_train(gp[f"b{i}"], x, cfg, kind)
            return x, None
        if remat:
            scan_fn = jax.checkpoint(scan_fn)
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(scan_fn, x, gp_stack, unroll=scan_unroll())
        return x

    return stage_fn


def stack_layer_stages(params, num_stages: int):
    """Reshape the (num_groups, ...) layer stack to (S, groups/S, ...) for
    the pipeline's stage-stacked params."""
    def reshape(a):
        g = a.shape[0]
        if g % num_stages:
            raise ValueError(
                f"num_groups={g} is not divisible by num_stages="
                f"{num_stages}; pick a stage count that divides the "
                "layer-group count (--stages for launch/train)")
        return a.reshape(num_stages, g // num_stages, *a.shape[1:])
    return jax.tree.map(reshape, params["layers"])


_TP_LAST_DIM = ("wq", "wk", "wv", "wi", "wg")


def tp_param_dims(stack):
    """The tensor-sharded dim per leaf of a layer stack (any number of
    leading scan/stage dims): wq/wk/wv and the MLP in-projections split
    on their OUT dim (column parallel), every ``wo`` on its IN dim (row
    parallel), and -1 (replicated) for the rest — the norm scales, whose
    tiny gradients all-reduce exactly via the shard_map transpose psum.
    Feeds ``tp_apply``'s ``param_dims`` / the pipeline's tp specs.
    """
    def dim(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in _TP_LAST_DIM:
            return leaf.ndim - 1
        if name == "wo":
            return leaf.ndim - 2
        return -1
    flat, treedef = jax.tree_util.tree_flatten_with_path(stack)
    return jax.tree_util.tree_unflatten(treedef,
                                        [dim(p, l) for p, l in flat])


def tp_sites(cfg: ModelConfig, groups: Optional[int] = None) -> int:
    """All-gather cut points per forward pass: 2 per block (attention +
    MLP in-gathers) — the ``sites`` count for ``init_tp_state``."""
    g = cfg.num_groups if groups is None else groups
    return 2 * len(cfg.layer_kinds()) * g


def tp_stage_stack_fn(cfg: ModelConfig, tpc, remat: bool = True):
    """``stage_fn(gp_stack, x, resid, mirror) -> (x, resid, mirror)`` —
    the tensor-parallel twin of :func:`stage_stack_fn` (``remat`` alike),
    run INSIDE the tensor ``shard_map`` (transport.tp_collectives.tp_apply
    or the 3D pipeline): ``x`` is the sequence-sharded residual,
    ``gp_stack`` the tp-local weight shards, and ``resid``/``mirror`` the
    site-stacked feedback buffers (or size-0 placeholders for feedback
    "none")."""
    kinds = cfg.layer_kinds()
    for kind in kinds:
        if kind not in B.TP_BLOCK_KINDS:
            raise ValueError(
                f"tensor parallelism covers the dense family "
                f"{B.TP_BLOCK_KINDS}; layer kind {kind!r} shards "
                f"differently (expert/state parallel) — run it with tp=1")
    nb = len(kinds)

    def stage_fn(gp_stack, x, resid, mirror):
        if tpc.feedback == "none":
            def scan_fn(x, gp):
                for i, kind in enumerate(kinds):
                    x, _ = B.attn_block_train_tp(gp[f"b{i}"], x, cfg, kind,
                                                 tpc)
                return x, None
            if remat:
                scan_fn = jax.checkpoint(scan_fn)
            with jax.named_scope("layers"):
                x, _ = jax.lax.scan(scan_fn, x, gp_stack,
                                    unroll=scan_unroll())
            return x, resid, mirror

        st = resid if tpc.feedback == "ef" else mirror
        g = jax.tree.leaves(gp_stack)[0].shape[0]
        st_g = st.reshape(g, 2 * nb, *st.shape[1:])

        def scan_fn(x, inp):
            gp, stb = inp
            outs = []
            for i, kind in enumerate(kinds):
                x, (b1, b2) = B.attn_block_train_tp(
                    gp[f"b{i}"], x, cfg, kind, tpc,
                    bufs=(stb[2 * i], stb[2 * i + 1]))
                outs += [b1, b2]
            return x, jnp.stack(outs)

        if remat:
            scan_fn = jax.checkpoint(scan_fn)
        with jax.named_scope("layers"):
            x, st_out = jax.lax.scan(scan_fn, x, (gp_stack, st_g),
                                     unroll=scan_unroll())
        st_out = st_out.reshape(st.shape)
        if tpc.feedback == "ef":
            return x, st_out, mirror
        return x, resid, st_out

    return stage_fn


@scope("vocab")
def hidden_lm_loss(params, x, labels, cfg: ModelConfig,
                   mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Chunked cross-entropy straight from hidden states: the (B,S,V)
    logits are never materialized — each sequence chunk's logits are
    computed, reduced, and REMATERIALIZED in backward (jax.checkpoint).
    Standard large-vocab technique; keeps loss-path peak memory at one
    chunk regardless of vocab size."""
    b, s, d = x.shape
    chunk = s if s <= 512 else max(512, s // 16)
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)

    @jax.checkpoint
    def chunk_nll(xc, lc, mc):
        logits = _lm_logits(params, xc, cfg)
        return (_fused_xent(logits, lc) * mc).sum()

    total = jnp.float32(0.0)
    for i in range(0, s, chunk):
        total = total + chunk_nll(x[:, i:i + chunk], labels[:, i:i + chunk],
                                  mask[:, i:i + chunk])
    return total / jnp.maximum(mask.sum(), 1.0)


def forward_eval(params, batch, cfg: ModelConfig,
                 policy: CompressionPolicy = NO_POLICY,
                 compress: bool = True, wire: bool = False):
    """``wire=True`` routes stage cuts through the wire-codec registry
    (pack -> unpack per request) instead of the in-process ``boundary_eval``
    — what the serve engines do (see core/boundary.boundary_wire_eval)."""
    kinds = cfg.layer_kinds()
    beval = boundary_wire_eval if wire else boundary_eval
    x = _embed_input(params, batch, cfg)
    segs = segment_bounds(cfg.num_groups, policy.num_stages)
    for si, (g0, g1) in enumerate(segs):
        def scan_fn(x, gp):
            for i, kind in enumerate(kinds):
                x, _ = B.block_train(gp[f"b{i}"], x, cfg, kind)
            return constrain(x, "batch", "model", None), None
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(scan_fn, x,
                                _slice_groups(params["layers"], g0, g1),
                                unroll=scan_unroll())
        if si < len(segs) - 1:
            x = beval(policy.at(si), x, compress)
    return _lm_logits(params, x, cfg)


# ---------------------------------------------------------------------------
# Inference: prefill + decode with per-group caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, cache_len: int, dtype=DTYPE):
    kinds = cfg.layer_kinds()
    caches = {}
    for i, kind in enumerate(kinds):
        def one(_):
            return B.block_cache(cfg, kind, batch, cache_len, dtype)
        caches[f"b{i}"] = jax.vmap(one)(jnp.arange(cfg.num_groups))
    return caches


def prefill(params, batch, cfg: ModelConfig,
            policy: CompressionPolicy = NO_POLICY, cache_len: int = 0,
            compress: bool = True, pad_len=None, wire: bool = False):
    """``pad_len``: optional (B,) int32 — the first pad_len[b] positions
    are left-padding (mixed-length serving batches) and are masked out of
    attention in every layer.  ``wire=True``: stage cuts pack/unpack the
    real codec payloads (see forward_eval)."""
    kinds = cfg.layer_kinds()
    beval = boundary_wire_eval if wire else boundary_eval
    x = _embed_input(params, batch, cfg)
    cache_len = cache_len or x.shape[1]
    segs = segment_bounds(cfg.num_groups, policy.num_stages)
    cache_segs = []
    pad_mask = None
    if pad_len is not None:
        pad_mask = jnp.arange(x.shape[1])[None, :] >= pad_len[:, None]

    for si, (g0, g1) in enumerate(segs):
        def scan_fn(x, gp):
            cs = {}
            for i, kind in enumerate(kinds):
                x, c, _ = B.block_prefill(gp[f"b{i}"], x, cfg, kind,
                                          cache_len, pad_mask=pad_mask)
                cs[f"b{i}"] = c
            return constrain(x, "batch", "model", None), cs
        with jax.named_scope("layers"):
            x, cseg = jax.lax.scan(scan_fn, x,
                                   _slice_groups(params["layers"], g0, g1),
                                   unroll=scan_unroll())
        cache_segs.append(cseg)
        if si < len(segs) - 1:
            x = beval(policy.at(si), x, compress)
    caches = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                          *cache_segs)
    return _lm_logits(params, x[:, -1:], cfg), caches


def decode_step(params, token, caches, pos, cfg: ModelConfig,
                policy: CompressionPolicy = NO_POLICY, compress: bool = True,
                pad_len=None, wire: bool = False):
    """token: (B,) int32; pos: scalar int32 OR (B,) int32 per-slot decode
    positions (continuous batching).  Returns (logits, new_caches).
    ``pad_len``: optional (B,) int32 left-padding lengths (see prefill);
    ``wire=True``: stage cuts pack/unpack the real codec payloads."""
    kinds = cfg.layer_kinds()
    beval = boundary_wire_eval if wire else boundary_eval
    with jax.named_scope("vocab"):
        x = params["embed"][token][:, None].astype(DTYPE)
    x = constrain(x, "batch", None, "model")
    segs = segment_bounds(cfg.num_groups, policy.num_stages)
    new_segs = []
    for si, (g0, g1) in enumerate(segs):
        def scan_fn(x, gp_cache):
            gp, cache = gp_cache
            new_c = {}
            for i, kind in enumerate(kinds):
                x, c = B.block_decode(gp[f"b{i}"], x, cache[f"b{i}"], pos,
                                      cfg, kind, pad_len=pad_len)
                new_c[f"b{i}"] = c
            return constrain(x, "batch", "model", None), new_c
        with jax.named_scope("layers"):
            x, nseg = jax.lax.scan(
                scan_fn, x, (_slice_groups(params["layers"], g0, g1),
                             _slice_groups(caches, g0, g1)),
                unroll=scan_unroll())
        new_segs.append(nseg)
        if si < len(segs) - 1:
            x = beval(policy.at(si), x, compress)
    new_caches = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                              *new_segs)
    return _lm_logits(params, x, cfg)[:, 0], new_caches


def decode_span(params, tokens, caches, pos, cfg: ModelConfig,
                policy: CompressionPolicy = NO_POLICY, compress: bool = True,
                pad_len=None, page_map=None, valid_len=None,
                wire: bool = True):
    """Multi-token decode: ``tokens`` (B, T) occupy absolute positions
    ``pos[b] + arange(T)``; K/V for all T tokens are written into the cache
    and logits are returned for EVERY position — (B, T, V).

    One program shape serves both halves of the serving stack:
      * chunked prefill — B=1, T=chunk, ``valid_len`` masking the padded
        tail of the final chunk (the last valid logit seeds generation);
      * speculative verification — B=slots, T=k+1, the target scoring the
        draft's k proposals plus the bonus position in ONE forward.

    ``caches``: the slab layout (leaves (G, B, C, ...)) or — with
    ``page_map`` (B, n_pages) — a page pool (leaves (G, N, P, ...)), see
    attention.attn_decode_span.

    Stage cuts pack per (request, token) when ``wire`` is set
    (boundary_wire_eval_tokens) — the same payload granularity as a T=1
    decode tick, so span logits match per-token decode bit-for-bit.
    """
    if compress and not wire:
        raise NotImplementedError(
            "decode_span compresses through the wire codecs only "
            "(wire=True) — the serve engines never use the in-process "
            "boundary at decode time")
    kinds = cfg.layer_kinds()
    with jax.named_scope("vocab"):
        x = params["embed"][tokens].astype(DTYPE)         # (B, T, d)
    x = constrain(x, "batch", None, "model")
    segs = segment_bounds(cfg.num_groups, policy.num_stages)
    new_segs = []
    for si, (g0, g1) in enumerate(segs):
        def scan_fn(x, gp_cache):
            gp, cache = gp_cache
            new_c = {}
            for i, kind in enumerate(kinds):
                x, c = B.block_decode_span(
                    gp[f"b{i}"], x, cache[f"b{i}"], pos, cfg, kind,
                    pad_len=pad_len, page_map=page_map, valid_len=valid_len)
                new_c[f"b{i}"] = c
            return constrain(x, "batch", "model", None), new_c
        with jax.named_scope("layers"):
            x, nseg = jax.lax.scan(
                scan_fn, x, (_slice_groups(params["layers"], g0, g1),
                             _slice_groups(caches, g0, g1)),
                unroll=scan_unroll())
        new_segs.append(nseg)
        if si < len(segs) - 1:
            x = boundary_wire_eval_tokens(policy.at(si), x, compress)
    new_caches = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                              *new_segs)
    return _lm_logits(params, x, cfg), new_caches


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def _pick_label_logit(logits, labels):
    """logits[..., labels] via a masked reduction instead of
    take_along_axis: gathers along a vocab dim the SPMD partitioner has
    sharded (lm head tied to a tensor-sharded embed) miscompile on some
    backends, while select+sum partitions as plain elementwise+reduce.
    Bitwise identical — every non-label slot contributes an exact 0."""
    v = logits.shape[-1]
    hit = labels[..., None] == jnp.arange(v, dtype=labels.dtype)
    return jnp.where(hit, logits, jnp.zeros((), logits.dtype)) \
        .sum(-1).astype(jnp.float32)


@jax.custom_vjp
def _fused_xent(logits, labels):
    """Per-token -log p[label] without materializing fp32 (B,S,V).

    Forward: logsumexp + masked label pick (reduce-fused upcasts only).
    Backward: dlogits = (softmax - onehot) * g, recomputed from the saved
    bf16 logits + fp32 lse — ONE (B,S,V) temp in logits dtype.
    """
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return lse - _pick_label_logit(logits, labels)


def _fx_fwd(logits, labels):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return lse - _pick_label_logit(logits, labels), (logits, labels, lse)


def _fx_bwd(res, g):
    logits, labels, lse = res
    probs = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    dlogits = ((probs - onehot) * g[..., None]).astype(logits.dtype)
    return dlogits, None


_fused_xent.defvjp(_fx_fwd, _fx_bwd)


@scope("vocab")
def lm_loss(logits: jnp.ndarray, labels: jnp.ndarray,
            mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Next-token cross entropy.  logits: (B,S,V); labels: (B,S).

    Processed in sequence chunks so the fp32 elementwise intermediates over
    (B, S_chunk, V) stay bounded even on backends with weak elementwise
    fusion (the host CPU used for dry-run memory accounting)."""
    s = labels.shape[1]
    chunk = s if s <= 512 else max(512, s // 8)
    nlls = [_fused_xent(logits[:, i:i + chunk], labels[:, i:i + chunk])
            for i in range(0, s, chunk)]
    nll = jnp.concatenate(nlls, axis=1) if len(nlls) > 1 else nlls[0]
    if mask is None:
        return nll.mean()
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
