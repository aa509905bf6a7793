"""Device self time a training step in the optimizer: gradient clipping and
AdamW (repro/optim/optimizers.py apply_updates, scope `optimizer`), in
ms: all phases, the mean over the cell's devices over the traced steps
(bench/scopes.py). No reading where the window holds no op of the scope."""
from bench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "optimizer")
