"""Share of the traced training window in which no op ran on the device,
in percent: 1 - (union of the device's op intervals) / window, the mean
over the cell's devices."""
from bench import trace


def read(ctx):
    tr = ctx.layer.get("trace")
    if not tr or not tr["devices"]:
        return None
    return trace.idle_pct(tr)
