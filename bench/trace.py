"""Device trace: capture with ``jax.profiler`` and reduce to intervals.

A capture is normalised to plain lists so that the reduction can be
checked on a small recorded trace (bench/tests/data/):

    {"window": [start_ns, end_ns],             # the harness's traced window
     "devices": {"0": [[name, start_ns, dur_ns], ...], ...},   # XLA ops
     "host": [[name, start_ns, dur_ns], ...]}  # the harness's bench.* spans

Every reduction works on that form: the union of busy intervals, idle
share, kernel time by name, collective time during which nothing else
runs on the device, and the breakdown of the longest ops and idle gaps.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench.window"
COLLECTIVE_RE = re.compile(
    r"collective-permute|all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"ppermute|send|recv", re.I)


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------

def _device_plane_id(name: str):
    m = re.match(r"^/device:TPU:(\d+)$", name)
    return m.group(1) if m else None


def _op_line(plane):
    lines = {ln.name: ln for ln in plane.lines}
    if "XLA Ops" in lines:
        return lines["XLA Ops"]
    for n, ln in lines.items():
        if "Ops" in n:
            return ln
    return None


def short_name(text: str) -> str:
    """An XLA op event's name is its HLO text; keep the instruction name
    and its result type (``%fusion.922 = bf16[50257,768]``)."""
    return text.split("{", 1)[0][:160]


def op_name(name: str) -> str:
    """The instruction name alone: ``fusion.922``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> dict:
    """Normalise one ``.xplane.pb`` to the plain form above."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        dev = _device_plane_id(plane.name)
        if dev is not None:
            line = _op_line(plane)
            if line is not None:
                devices[dev] = [[short_name(e.name), float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, float(e.start_ns), float(e.duration_ns)]
                         for e in line.events if e.name.startswith("bench.")]
    win = [h for h in host if h[0] == WINDOW_SPAN]
    ops = [e for evs in devices.values() for e in evs]
    window = ([win[0][1], win[0][1] + win[0][2]] if win else
              [min(e[1] for e in ops), max(e[1] + e[2] for e in ops)])
    return {"window": window, "devices": devices, "host": host}


def collect(trace_dir: str) -> dict:
    """Load the capture just written under ``trace_dir`` and remove the
    directory, so that traced runs leave nothing on disk."""
    import shutil
    try:
        return load_xplane(newest_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def clip(ops: Iterable[Sequence], window: Sequence[float]) -> List[Interval]:
    """[start, end) of each op, clipped to the window."""
    lo, hi = window
    out = []
    for op in ops:
        s, e = max(op[1], lo), min(op[1] + op[2], hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy_ns(tr: dict, dev: str) -> float:
    return total(union(clip(tr["devices"][dev], tr["window"])))


def window_ns(tr: dict) -> float:
    return tr["window"][1] - tr["window"][0]


def mean_busy_s(tr: dict) -> float:
    devs = sorted(tr["devices"])
    if not devs:
        return 0.0
    return sum(busy_ns(tr, d) for d in devs) / len(devs) / 1e9


def idle_pct(tr: dict) -> float:
    """Mean over devices of 1 - busy / window, in percent."""
    return 100.0 * (1.0 - mean_busy_s(tr) * 1e9 / window_ns(tr))


def matching(tr: dict, dev: str, pattern) -> List[Sequence]:
    """The ops whose instruction name (not their operands) matches."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    return [op for op in tr["devices"][dev] if rx.search(op_name(op[0]))]


def kernel_ns(tr: dict, dev: str, pattern) -> Tuple[float, int]:
    """(device time, count) of the ops whose name matches, in the window."""
    ops = matching(tr, dev, pattern)
    return total(clip(ops, tr["window"])), len(clip(ops, tr["window"]))


def exposed_ns(tr: dict, dev: str, pattern=COLLECTIVE_RE) -> float:
    """Time of the matching (collective) ops during which no other op runs
    on the device."""
    coll = union(clip(matching(tr, dev, pattern), tr["window"]))
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    other = union(clip([op for op in tr["devices"][dev]
                        if not rx.search(op_name(op[0]))], tr["window"]))
    covered, j = 0.0, 0
    for s, e in coll:
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            covered += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return total(coll) - covered


def _group(name: str) -> str:
    """``%fusion.922 = bf16[50257,768]`` -> ``fusion bf16[50257,768]``."""
    head, _, ty = name.partition(" = ")
    return (re.sub(r"\.\d+$", "", head.lstrip("%")) + " " + ty).strip()


def self_times(ops: Sequence[Sequence], window) -> List[Tuple[str, float]]:
    """(name, time) per op inside the window, less the time of the ops
    nested in it (a while loop's body ops, say)."""
    evs = sorted(((max(o[1], window[0]), min(o[1] + o[2], window[1]), o[0])
                  for o in ops), key=lambda e: (e[0], -e[1]))
    out, stack = [], []
    for s, e, name in evs:
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][2]][1] -= min(e, stack[-1][1]) - s
        out.append([name, e - s])
        stack.append((s, e, len(out) - 1))
    return [(n, t) for n, t in out]


def breakdown(tr: dict, top: int = 10) -> dict:
    """The device ops that took most time (self time, grouped by
    instruction name without its number and by result type, summed over
    devices and divided by their number) and the longest idle gaps, each
    named by the harness span it fell in."""
    devs = sorted(tr["devices"])
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    by: Dict[str, float] = {}
    for d in devs:
        for name, t in self_times(tr["devices"][d], tr["window"]):
            g = _group(name)
            by[g] = by.get(g, 0.0) + t
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for d in devs[:1]:
        busy = union(clip(tr["devices"][d], tr["window"]))
        edges = [tr["window"][0]] + [x for iv in busy for x in iv] \
            + [tr["window"][1]]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    spans = [h for h in tr["host"] if h[0] != WINDOW_SPAN]

    def host_at(s, e):
        mid = 0.5 * (s + e)
        inside = [h for h in spans if h[1] <= mid < h[1] + h[2]]
        return min(inside, key=lambda h: h[2])[0] if inside else "host:none"

    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v / len(devs) / 1e9] for n, v in ops],
            "idle_gaps": [[host_at(s, e), (e - s) / 1e9] for s, e in gaps]}
