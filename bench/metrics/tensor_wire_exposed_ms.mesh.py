"""Exposed time a training step of the tensor-parallel ring, in ms: its
gathers' and scatters' collectives (scope `tensor_wire`,
repro/transport/tp_collectives.py) while no op outside the ring runs, the
mean over the cell's devices over the traced steps (bench/rings.py). No
reading where the program has no op under the scope."""
from bench import rings


def read(ctx):
    return rings.exposed_ms(ctx, "tensor_wire")
