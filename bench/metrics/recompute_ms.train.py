"""Device self time a training step in remat's recompute of the
checkpointed forward (ops under `rematted_computation`), in ms: all
layers, the mean over the cell's devices over the traced steps
(bench/scopes.py). No reading where the window holds no op of the phase."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "recompute")
