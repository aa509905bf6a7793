"""Operations and bytes the algorithm needs, computed from shapes.

* Model FLOPs per trained token: PaLM's count (Chowdhery et al. 2022,
  appendix B), ``6 N + 12 L H Q S``: N is every matmul weight of the
  layers plus the LM head (the embedding lookup is a gather and does not
  count, nor do norm scales), H Q the attention width, S the sequence.
  Recomputed (rematerialized) work does not count.
* Bytes a wire kernel needs: its input read once and its output written
  once.  The simulated boundary's kernels (kernels/quantize.py
  ``quant_dequant`` and kernels/topk_mask.py ``topk_block``) read the
  boundary tensor and write the compressed-then-restored tensor of the
  same shape and dtype.
"""
from __future__ import annotations

from bench.configs import sizes


def matmul_params(conf: dict) -> int:
    """N: matmul weights of the layers plus the LM head."""
    s = sizes(conf)
    d, hd = s["d_model"], s["head_dim"]
    attn = d * hd * s["num_heads"] * 2 + d * hd * s["num_kv_heads"] * 2
    mlp = (3 if s["mlp"] == "swiglu" else 2) * d * s["d_ff"]
    return s["num_layers"] * (attn + mlp) + s["vocab_size"] * d


def train_flops_per_token(conf: dict, seq: int) -> int:
    s = sizes(conf)
    width = s["num_heads"] * s["head_dim"]
    return 6 * matmul_params(conf) + 12 * s["num_layers"] * width * seq


def boundary_kernel_bytes(batch: int, seq: int, d_model: int,
                          elem_bytes: int = 2) -> int:
    """One simulated-boundary kernel call on a (batch, seq, d_model)
    tensor: read it once, write its restored copy once."""
    return 2 * batch * seq * d_model * elem_bytes
