"""Collective time a training step on the mesh during which no other op
runs on the device, in ms: every collective (stage hops, tensor-parallel
gathers and scatters, the partitioner's reductions), the mean over the
cell's devices over the traced steps (bench/trace.exposed_ns). No
reading in a trace without collectives."""
from bench import rings


def read(ctx):
    return rings.collective_exposed_ms(ctx)
