"""Bytes the codec kernels of the pipeline mesh need a step, from the
program's own count of what each ring carries
(``repro.train.steps.mesh_wire_bytes``, left by bench/drivers/train_mesh.py
in ``ctx.layer["mesh_wire"]``).

A ring whose payloads take the Pallas route packs each hop with one
kernel call (kernels/quantize.py ``quantize_wire`` for q8): the call
reads the hop's elements as the float32 the codec hands it and writes
the payload once.  Unpacking is plain XLA (``dequantize_wire``), and a
ring on the jnp route (the tensor ring's one-row payloads) runs no
kernel, so neither counts here.
"""
from __future__ import annotations

F32_BYTES = 4


def _kernel_rings(mesh_wire: dict):
    return [r for r in mesh_wire.values() if r["route"] == "pallas"]


def kernel_calls(mesh_wire: dict) -> int:
    """Codec kernel calls one device makes a step."""
    return sum(r["hops"] for r in _kernel_rings(mesh_wire))


def kernel_bytes(mesh_wire: dict) -> int:
    """Bytes those calls read and write, one device, one step."""
    return sum(r["hops"] * (F32_BYTES * r["elems_per_hop"]
                            + r["payload_bytes_per_hop"])
               for r in _kernel_rings(mesh_wire))
