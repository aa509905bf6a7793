"""Exposed time a training step of the pipeline's stage ring, in ms: its
collectives (scope `stage_wire`, repro/transport/pipeline.py) while no
op outside the ring runs, the mean over the cell's devices over the traced
steps (bench/rings.py). No reading where the program has no op under
the scope."""
from bench import rings


def read(ctx):
    return rings.exposed_ms(ctx, "stage_wire")
