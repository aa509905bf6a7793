"""Pallas TPU kernel: causal self-attention, blocked with an online softmax.

A thin wrapper around JAX's bundled splash-attention kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``).  The scores of one
(query block, key block) pair live only in VMEM: QK^T accumulates in f32,
the softmax runs online over key blocks, and blocks above the diagonal are
skipped.  Its backward pass is a kernel of its own (dq, dk and dv in one
pass), so no ``[S, S]`` score tensor is ever written to HBM, forward or
backward.

Layout: q ``(B, KV, G, S, hd)`` -- the G = H / KV query heads that share
each key/value head -- and k, v ``(B, KV, S, hd)``.  Each (batch, KV head)
is one multi-query problem: the kernel runs over its G query heads and
is vmapped over batch and KV heads, so G = 1 (multi-head) and G > 1
(grouped-query) take one path.  q comes pre-scaled by ``1/sqrt(hd)``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

# Query and key block edge, largest first: the first that divides the
# sequence is taken.  Blocks are multiples of the 128-lane tile.  On a
# v5e, whole training steps ran fastest with 1024 both at S 1024, hd 64
# and at S 4096, hd 128 (PERF.md, section 6).
BLOCKS = (1024, 512, 256, 128)
# head widths the kernel has been compiled and measured at
HEAD_DIMS = (64, 128)


def flash_block(s: int, hd: int) -> Optional[int]:
    """The block edge for a causal ``s``-token sequence with ``hd``-wide
    heads, or None where the kernel does not take the shape (the caller
    stays dense)."""
    if hd not in HEAD_DIMS:
        return None
    for b in BLOCKS:
        if b <= s and s % b == 0:
            return b
    return None


@functools.lru_cache(maxsize=None)
def _kernel(s: int, g: int, block: int, interpret: bool):
    """The splash kernel for one (batch, KV head): a causal ``(s, s)`` mask
    over ``g`` query heads.  Built once per shape: the mask's block tables
    are computed on the host, and every layer and every trace reuses
    them.  Built eagerly even inside a trace, so the cached tables are
    concrete arrays and never a tracer of the first caller."""
    mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * g)
    # one backward kernel computes dq beside dk and dv
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask, block_sizes=sizes, interpret=interpret)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Causal attention of q ``(B, KV, G, S, hd)`` (pre-scaled) over k, v
    ``(B, KV, S, hd)``; returns ``(B, KV, G, S, hd)`` in q's dtype."""
    _, _, g, s, hd = q.shape
    block = flash_block(s, hd)
    if block is None:
        raise ValueError(f"the kernel takes no {s}-token sequence of "
                         f"{hd}-wide heads (blocks {BLOCKS}, heads "
                         f"{HEAD_DIMS})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = _kernel(s, g, block, interpret)
    return jax.vmap(jax.vmap(kernel))(q, k, v)
