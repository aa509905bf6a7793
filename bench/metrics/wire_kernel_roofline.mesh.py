"""The mesh's codec kernels' share of their roofline, in percent: the
bytes they need a step (bench/wire_bytes.py, from the program's count of
each ring's hops) over the chip's HBM bandwidth (bench/peaks.json),
divided by their device time in the trace.  These kernels do a few
operations per byte, so bandwidth bounds them.  Read only when the trace
holds exactly the expected number of kernel calls."""
import importlib.util
import os

from bench import wire_bytes

_spec = importlib.util.spec_from_file_location(
    "bench_metric_wire_kernel_ms_mesh",
    os.path.join(os.path.dirname(__file__), "wire_kernel_ms.mesh.py"))
_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ms)


def read(ctx):
    layer = ctx.layer
    tr = layer.get("trace")
    if not tr or not tr["devices"] or "mesh_wire" not in layer:
        return None
    t_ns, n = _ms.kernel_time_ns(tr)
    steps = layer["steps"]
    calls = wire_bytes.kernel_calls(layer["mesh_wire"])
    if t_ns <= 0 or n != calls * steps:
        return None
    need_s = wire_bytes.kernel_bytes(layer["mesh_wire"]) * steps / ctx.peak[
        "hbm_bytes_per_s"]
    return 100.0 * need_s / (t_ns / 1e9)
