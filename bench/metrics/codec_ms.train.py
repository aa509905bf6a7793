"""Device self time a training step in the stage-cut codecs: the wire
kernels and the copies and reductions XLA places around them
(repro/models/transformer.py forward_hidden, repro/transport/codecs.py,
scope `codec`), in ms: all phases, the mean over the cell's devices over
the traced steps (bench/scopes.py). No reading where the window holds no
op of the scope."""
from bench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "codec")
