"""The stage-cut codec kernels' share of their roofline, in percent: the
bytes they need (each call reads its input and writes its output once,
bench/flops.py) over the chip's HBM bandwidth (bench/peaks.json), divided
by their device time in the trace.  These kernels do a few operations
per byte, so bandwidth bounds them.  Read only when the trace holds
exactly the expected number of kernel calls."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_wire_kernel_ms_train",
    os.path.join(os.path.dirname(__file__), "wire_kernel_ms.train.py"))
_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ms)


def read(ctx):
    layer = ctx.layer
    tr = layer.get("trace")
    if not tr or not tr["devices"] or "wire_bytes_per_step" not in layer:
        return None
    t_ns, n = _ms.kernel_time_ns(tr)
    steps = layer["steps"]
    if t_ns <= 0 or n != layer["wire_calls_per_step"] * steps:
        return None
    need_s = layer["wire_bytes_per_step"] * steps / ctx.peak[
        "hbm_bytes_per_s"]
    return 100.0 * need_s / (t_ns / 1e9)
