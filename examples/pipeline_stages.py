"""Real pipeline parallelism with compressed stage handoffs (beyond-paper).

Forces 4 host devices, builds a 4-stage pipeline over mesh axis "stage" via
shard_map, and streams microbatches through it with the boundary payload
PACKED on the wire (bf16 raw / int8 quant / 4-bit packed / TopK
values+indices).  Verifies the pipelined result matches the sequential
forward and prints the measured bytes-per-boundary of each scheme — the
collective-bytes reduction that motivates the whole paper — then demos the
pluggable schedules (repro.transport.schedules): 1F1B (fused single-buffer
hops, rematerialized ticks) and interleaved virtual stages (each device
runs 2 round-robin stage slices: 1/v the fill bubble, v*S-1 compressed
cuts).

Run:  PYTHONPATH=src python examples/pipeline_stages.py
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch.mesh import make_mesh
from repro.transport import (get_schedule, pack_payload, pipeline_forward,
                             wire_bytes)

mesh = make_mesh((4,), ("stage",))
B, D = 8, 256
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (B, D), jnp.float32)

# 4 stages, each an MLP block; stage s holds slice s of the stacked params.
k1, k2 = jax.random.split(key)
w1 = jax.random.normal(k1, (4, D, 4 * D)) * (1.0 / D) ** 0.5
w2 = jax.random.normal(k2, (4, 4 * D, D)) * (1.0 / (4 * D)) ** 0.5
params = {"w1": w1, "w2": w2}


def stage_fn(p, h):
    return h + jax.nn.gelu(h @ p["w1"]) @ p["w2"]


# sequential reference
ref = x
for s in range(4):
    ref = stage_fn(jax.tree.map(lambda a: a[s], params), ref)

print(f"pipeline over mesh {dict(mesh.shape)} — payload schemes:")
for scheme, k in [("none", 0.1), ("q8", 0.1), ("q4", 0.1), ("topk", 0.1)]:
    out = pipeline_forward(stage_fn, params, x, mesh, "stage",
                           scheme=scheme, k_frac=k)
    err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    payload = pack_payload(ref[: B // 4], scheme, k)
    mb = wire_bytes(payload)
    raw = ref[: B // 4].size * 2
    print(f"  {scheme:5s}: bytes/boundary {mb:7d} "
          f"({raw / mb:4.1f}x vs bf16)  rel-err vs sequential {err:.3f}")
print("-> 'none' must be ~exact; q8 tight; q4/topk lossy by design")

# --- pluggable schedules -----------------------------------------------------
print("\nschedules (mb=8 microbatches on 4 stages):")
out_1f1b = pipeline_forward(stage_fn, params, x, mesh, "stage", scheme="q8",
                            microbatches=8, schedule="1f1b")
print(f"  1f1b       : {get_schedule('1f1b').describe(8, 4)}  "
      f"rel-err {float(jnp.max(jnp.abs(out_1f1b - ref)) / jnp.max(jnp.abs(ref))):.3f}")

# interleaved: 8 LOGICAL stage slices (2 per device, round-robin).  To
# keep the same total model as the 4-stage reference, interleave the 4
# real slices with 4 IDENTITY slices (zero-weight residual MLPs):
# logical order [real0, id, real1, id, real2, id, real3, id].
params8 = {"w1": jnp.concatenate([w1, jnp.zeros_like(w1)]),
           "w2": jnp.concatenate([w2, jnp.zeros_like(w2)])}
order = np.array([0, 4, 1, 5, 2, 6, 3, 7])
params8 = jax.tree.map(lambda a: a[order], params8)
out_il = pipeline_forward(stage_fn, params8, x, mesh, "stage", scheme="q8",
                          microbatches=8, schedule="interleaved",
                          virtual_stages=2)
err = float(jnp.max(jnp.abs(out_il - ref)) / jnp.max(jnp.abs(ref)))
print(f"  interleaved: {get_schedule('interleaved', 2).describe(8, 4)}  "
      f"rel-err {err:.3f}")
print("-> interleaved shrinks the fill bubble by 1/v and multiplies the "
      "compressed cuts — the regime where the codecs pay off")
