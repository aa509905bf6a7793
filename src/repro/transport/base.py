"""The transport interface: what crosses a pipeline-stage cut, both ways.

A :class:`Transport` realizes ONE boundary of a :class:`CompressionPolicy`:

  ``fw(x, fw_buf, ids)  -> (message, new_fw_buf, ctx)``
      the forward activation crossing the cut (feedback-wrapped compressor);
      ``ctx`` carries whatever the backward direction needs (e.g. the
      forward TopK mask / indices for ``reuse_indices``).

  ``bw(g, bw_buf, ctx)  -> (grad_message, new_bw_buf)``
      the backward activation-gradient crossing the cut in the reverse
      direction.

Two implementations exist:

  * :class:`repro.transport.simulated.SimulatedTransport` — single-device,
    convergence-faithful (the paper's Sec. 2.1 setup); used inside the
    ``jax.custom_vjp`` boundary in core/boundary.py.
  * :class:`repro.transport.pipeline.PipelineTransport` — the real
    ``shard_map``/``ppermute`` path: packed payloads on the wire in both
    directions, with per-stage feedback buffers threaded through the
    pipeline scan (``fw_hop``/``bw_hop`` extend fw/bw with the buffer
    slice bookkeeping; delta-coded modes add receiver-side mirrors).

Both consume the same wire-codec registry (transport/codecs.py), so the
simulated C(x) and the real packed bytes round-trip identically.

Error feedback is wire-cost-free: EF packs the compensated tensor
``x + e`` (same codec, same bytes), EF-mixed packs two half-K payloads
(k/2 + k/2 = k), and EF21/AQ-SGD pack the delta ``x - buf`` (again one
codec payload) — so :meth:`Transport.wire_bytes_per_example` holds for
every feedback mode, which the pipeline_wire benchmark asserts.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import BoundaryPolicy
from repro.transport.codecs import WireCodec, codec_for


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (payload pytrees
    confuse it).  Shared by the pipeline transport and the DP/TP
    collectives."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class Transport:
    """One stage cut: a forward and a backward wire direction."""

    policy: BoundaryPolicy

    def fw(self, x: jnp.ndarray, fw_buf=None, ids=None
           ) -> Tuple[jnp.ndarray, Any, Any]:
        raise NotImplementedError

    def bw(self, g: jnp.ndarray, bw_buf=None, ctx=None
           ) -> Tuple[jnp.ndarray, Any]:
        raise NotImplementedError

    # -- wire accounting (shared by benchmarks) -----------------------------

    def fw_codec(self) -> Optional[WireCodec]:
        try:
            return codec_for(self.policy.fw)
        except ValueError:
            return None

    def bw_codec(self) -> Optional[WireCodec]:
        try:
            return codec_for(self.policy.bw)
        except ValueError:
            return None

    def wire_bytes_per_example(self, n: int, elem_bytes: int = 2
                               ) -> Tuple[float, float]:
        """(fw, bw) modeled bytes for one example's boundary tensor of
        ``n`` flattened elements (excl. per-tensor scale overhead)."""
        fw_c, bw_c = self.fw_codec(), self.bw_codec()
        fw = (fw_c.wire_bytes_per_elem(n, elem_bytes, self.policy.fw.k_frac)
              * n if fw_c else float("nan"))
        if self.policy.reuse_indices and bw_c is not None:
            # indices already live at both ends after the forward send: the
            # backward payload is values only, and its length is set by the
            # FORWARD pack's k (the reused indices), not the bw compressor.
            bw = self.policy.fw.k_frac * n * elem_bytes
        else:
            bw = (bw_c.wire_bytes_per_elem(n, elem_bytes,
                                           self.policy.bw.k_frac) * n
                  if bw_c else float("nan"))
        return fw, bw
