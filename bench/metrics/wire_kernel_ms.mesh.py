"""Device time of the mesh's codec kernels per training step, in ms: the
summed duration of the Pallas wire kernels' ops in the traced window
(mean over devices) over the window's steps.  The kernels are found by
name; a trace with none of them gives no reading."""
from bench import trace

# the pallas_call names of the wire kernels a ring's hop can run
# (repro/kernels/quantize.py quantize_wire: q8 packs of 8 rows or more)
KERNELS = r"quantize_wire"


def kernel_time_ns(tr):
    """(mean device time, mean op count) of the wire kernels."""
    devs = sorted(tr["devices"])
    got = [trace.kernel_ns(tr, d, KERNELS) for d in devs]
    return (sum(t for t, _ in got) / len(devs),
            sum(n for _, n in got) / len(devs))


def read(ctx):
    tr = ctx.layer.get("trace")
    if not tr or not tr["devices"]:
        return None
    t, n = kernel_time_ns(tr)
    if n == 0:
        return None
    return t / 1e6 / ctx.layer["steps"]
