"""Device self time a training step in the backward pass (ops under an
autodiff `transpose(`, less remat's recompute), in ms: all layers, the
mean over the cell's devices over the traced steps (bench/scopes.py). No
reading where the window holds no op of the phase."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "backward")
