"""Device self time a training step in attention: the QKV projections,
RoPE, scores, softmax, PV and output projection
(repro/models/attention.py, scope `attn`), in ms: all phases, the mean
over the cell's devices over the traced steps (bench/scopes.py). No
reading where the window holds no op of the scope."""
from bench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "attn")
