"""The benchmark harness: finds a cell's files by name, checks the device,
runs the cell's driver and prints the one result line.

Everything that belongs to one cell, configuration or per-layer metric is
a file of its own, found by the name in BENCHMARK.json:

* ``bench/workloads/<cell>.json``  the cell: its driver, configuration,
  traffic and the limits of its correctness check;
* ``bench/configs/<config>.json``  the configuration as run;
* ``bench/drivers/<driver>.py``     ``run(ctx) -> dict`` for a kind of cell;
* ``bench/metrics/<metric>.py``     ``read(ctx) -> float | None``, one
  per-layer metric from the trace and the harness's own spans.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import re
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown device, bad
    manifest)."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def cell_file(name: str, bench: str = BENCH) -> dict:
    return _load_json(os.path.join(bench, "workloads", f"{name}.json"))


def config_file(name: str, bench: str = BENCH) -> dict:
    return _load_json(os.path.join(bench, "configs", f"{name}.json"))


def _module(path: str, modname: str):
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None:
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[modname] = mod
    return mod


def mix_file(name: str, bench: str = BENCH) -> dict:
    return _load_json(os.path.join(bench, "mixes", f"{name}.json"))


def driver(name: str, bench: str = BENCH):
    return _module(os.path.join(bench, "drivers", f"{name}.py"),
                   f"bench_driver_{name}_{abs(hash(bench))}")


def metric_reader(name: str, bench: str = BENCH) -> Callable:
    mod = _module(os.path.join(bench, "metrics", f"{name}.py"),
                  "bench_metric_" + re.sub(r"\W", "_", name)
                  + f"_{abs(hash(bench))}")
    return mod.read


def cell_entry(man: dict, cell: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == cell:
            return w
    raise BenchError(f"no cell {cell!r} in BENCHMARK.json")


def end_to_end_for(man: dict, cell: str) -> List[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in man["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_for(man: dict, cell: str) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose ``moves`` the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(man, cell)}
    out = []
    for m in man["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def validate(man: dict, root: str = ROOT) -> List[str]:
    """Problems with the manifest and the files it names (empty if none)."""
    bench = os.path.join(root, "bench")
    bad = []
    names = []
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in man.get(sec, []):
            names.append((sec, e["name"]))
            if not NAME_RE.match(e["name"]):
                bad.append(f"{sec}: bad name {e['name']!r}")
            if "unit" in e and not UNIT_RE.match(e["unit"]):
                bad.append(f"{sec}: bad unit {e['unit']!r}")
            if "better" in e and e["better"] not in ("lower", "higher"):
                bad.append(f"{sec}: bad better {e['better']!r}")
    for sec in ("configs", "workloads"):
        seen = [n for s, n in names if s == sec]
        if len(seen) != len(set(seen)):
            bad.append(f"{sec}: duplicate names")
    metric_names = [n for s, n in names if s in ("end_to_end", "per_layer")]
    if len(metric_names) != len(set(metric_names)):
        bad.append("metrics: duplicate names")
    if not any(m["name"] == "setup_s" for m in man["end_to_end"]):
        bad.append("end_to_end: no setup_s")
    configs = {c["name"] for c in man["configs"]}
    used = {w["config"] for w in man["workloads"]}
    if configs != used:
        bad.append(f"configs not used by a cell: {sorted(configs - used)}")
    four = sum(1 for w in man["workloads"] if w["chips"] == 4)
    if four > max(1, len(man["workloads"]) // 2):
        bad.append(f"{four} cells ask for 4 chips")
    e2e_names = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        if m["moves"] not in e2e_names:
            bad.append(f"{m['name']}: moves unknown {m['moves']!r}")
        if not os.path.exists(os.path.join(bench, "metrics",
                                           f"{m['name']}.py")):
            bad.append(f"{m['name']}: no reader file")
    for w in man["workloads"]:
        cell = w["name"]
        if not os.path.exists(os.path.join(bench, "workloads",
                                           f"{cell}.json")):
            bad.append(f"{cell}: no workload file")
            continue
        cf = cell_file(cell, bench)
        if cf["config"] != w["config"] or cf["traffic"] != w["traffic"]:
            bad.append(f"{cell}: config or traffic differs from its file")
        if not os.path.exists(os.path.join(bench, "mixes",
                                           f"{w['traffic']}.json")):
            bad.append(f"{cell}: no traffic file {w['traffic']}")
        e2e = {m["name"] for m in end_to_end_for(man, cell)}
        if "setup_s" not in e2e or len(e2e) < 2:
            bad.append(f"{cell}: needs setup_s and another end-to-end metric")
        layer = per_layer_for(man, cell)
        if not layer:
            bad.append(f"{cell}: no per-layer metric")
        for m in layer:
            if m["moves"] not in e2e:
                bad.append(f"{cell}: reports {m['name']} but not its "
                           f"moves {m['moves']}")
    for c in man["configs"]:
        if not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"{c['name']}: no file {c['file']}")
    return bad


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def peaks() -> dict:
    return _load_json(os.path.join(BENCH, "peaks.json"))


def find_devices(chips: int):
    """The first ``chips`` TPU devices and their peak entry; raises
    BenchError (never falls back to the CPU)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    kind = devs[0].device_kind
    table = peaks()["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], table[kind]


def memory_peak_bytes(devices) -> int:
    return max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               for d in devices)


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

@dataclass
class Ctx:
    """What a driver is given, and what it leaves for the metric readers."""
    cell_name: str
    cell: dict
    conf_name: str
    conf: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peak: dict
    t0: float
    trace_dir: str = ""
    # left by the driver for the per-layer readers
    layer: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Check:
    """One number compared with its limit (``ok`` when value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def phase(ctx: "Ctx", what: str) -> None:
    """Seconds since process start at the end of a phase, on stderr."""
    print(f"phase {what}: {time.perf_counter() - ctx.t0:.2f} s",
          file=sys.stderr, flush=True)


def run_cell(ctx: Ctx, man: dict, bench: str = BENCH) -> dict:
    """Drive the cell and assemble the result line."""
    out = driver(ctx.cell["driver"], bench).run(ctx)
    checks: List[Check] = out["checks"]
    if ctx.trace:
        metrics = {}
        for m in per_layer_for(man, ctx.cell_name):
            v = metric_reader(m["name"], bench)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in end_to_end_for(man, ctx.cell_name)}
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": bool(out["correct"] and all(c.ok for c in checks)),
            "attempted": int(out["attempted"]), "failed": int(out["failed"]),
            "metrics": metrics, "device": device}
    if ctx.trace:
        device["busy_s"] = ctx.layer["busy_s"]
        device["window_s"] = ctx.layer["window_s"]
        line["breakdown"] = ctx.layer["breakdown"]
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return line


def print_checks(checks: Dict[str, dict], stream=sys.stderr) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=stream, flush=True)
