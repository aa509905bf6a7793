"""Device self time a training step in the MLP (repro/models/common.py
mlp_apply, repro/models/moe.py moe_apply, scope `mlp`), in ms: all
phases, the mean over the cell's devices over the traced steps
(bench/scopes.py). No reading where the window holds no op of the scope."""
from bench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "mlp")
