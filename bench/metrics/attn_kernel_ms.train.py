"""Device time of the causal attention kernels per training step, in ms:
the summed duration of the splash kernels' ops (forward, and the fused
dq/dk/dv backward; repro/kernels/flash_attn.py) in the traced window
(mean over devices) over the window's steps.  A program that runs
attention without them (the dense route) gives no reading."""
from bench import trace

# the kernels' custom calls keep the bundled kernel's names:
# splash_mqa_fwd_residuals.N, splash_mqa_fwd_no_residuals.N,
# splash_mqa_dkv_no_residuals.N
KERNELS = r"splash_mqa_"


def read(ctx):
    tr = ctx.layer.get("trace")
    if not tr or not tr["devices"]:
        return None
    devs = sorted(tr["devices"])
    got = [trace.kernel_ns(tr, d, KERNELS) for d in devs]
    if sum(n for _, n in got) == 0:
        return None
    return sum(t for t, _ in got) / len(devs) / 1e6 / ctx.layer["steps"]
