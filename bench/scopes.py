"""Device time by the program's layers and phases.

The program names its layers with ``jax.named_scope`` (``vocab``,
``layers``, ``attn``, ``mlp``, ``norm``, ``codec``, ``optimizer``).  XLA
keeps the name path in each HLO instruction's ``op_name`` metadata, through
autodiff and rematerialisation:

    jit(step)/jvp(layers)/while/body/closed_call/attn/dot_general
    jit(step)/transpose(jvp(layers))/.../checkpoint/rematted_computation/mlp/..
    jit(step)/transpose(jvp(codec))/...

The device trace names an op by its instruction alone (``fusion.922``), so
the map from instruction to ``op_name`` is read from the compiled text of
the same step: the cell's driver builds the step again after the traced
window (``build(ctx)``), and its ``lower(...).compile()`` is the cached
executable that ran.  The map is kept in the normalised trace under
``"op_names"``.

Attribution: each device op's self time (``trace.self_times``) goes to

* a layer: the innermost component of its ``op_name`` path that equals a
  scope once the ``jvp(`` / ``transpose(`` wrappers are stripped (a whole
  component, never a substring: ``jit(norm)`` is not ``norm``), else
  ``unscoped``;
* a phase: ``optimizer`` for that layer, else ``recompute`` if the path
  holds ``rematted_computation``, else ``backward`` if it holds
  ``transpose(``, else ``forward``.

A program without the scopes (an older commit) reads as all ``unscoped``,
and the per-layer metrics built on this give no reading there.
"""
from __future__ import annotations

import re
import sys
import time
from typing import Dict, Optional

from bench import trace

SCOPES = ("vocab", "layers", "attn", "mlp", "norm", "codec", "optimizer")
UNSCOPED = "unscoped"
PHASES = ("forward", "backward", "recompute", "optimizer")

_WRAPPERS = ("jvp(", "transpose(")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction: op_name} of every instruction in a compiled HLO text
    ("" for one without the metadata)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line)
            out[m.group(1)] = n.group(1) if n else ""
    return out


def _bare(component: str) -> str:
    """``transpose(jvp(attn))`` -> ``attn``."""
    while True:
        for w in _WRAPPERS:
            if component.startswith(w) and component.endswith(")"):
                component = component[len(w):-1]
                break
        else:
            return component


def layer_of(op_name: str) -> str:
    for comp in reversed(op_name.split("/")):
        bare = _bare(comp)
        if bare in SCOPES:
            return bare
    return UNSCOPED


def phase_of(op_name: str) -> str:
    if layer_of(op_name) == "optimizer":
        return "optimizer"
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward"


def attribute(tr: dict, names: Dict[str, str]) -> dict:
    """Self time in the window by layer and by phase, in ns, the mean over
    devices, and the time of ops whose instruction the map lacks (they
    count as ``unscoped``)."""
    devs = sorted(tr["devices"])
    layer: Dict[str, float] = {}
    phase: Dict[str, float] = {}
    missing = 0.0
    for d in devs:
        for name, t in trace.self_times(tr["devices"][d], tr["window"]):
            instr = trace.op_name(name)
            if instr in names:
                full = names[instr]
                lay, ph = layer_of(full), phase_of(full)
            else:
                lay, ph = UNSCOPED, "forward"
                missing += t
            layer[lay] = layer.get(lay, 0.0) + t
            phase[ph] = phase.get(ph, 0.0) + t
    n = max(len(devs), 1)
    return {"layer": {k: v / n for k, v in layer.items()},
            "phase": {k: v / n for k, v in phase.items()},
            "missing_ns": missing / n}


def step_op_names(ctx) -> Dict[str, str]:
    """The op map of the cell's step, from its driver's ``build(ctx)``;
    empty (with the reason on stderr) where it cannot be had."""
    from bench import harness
    t0 = time.perf_counter()
    try:
        drv = harness.driver(ctx.cell["driver"])
        step, state, feed, ids, _ = drv.build(ctx)
        text = step.lower(*state, feed[0], ids).compile().as_text()
        del state, feed
    except Exception as e:  # the readers give no reading, the run goes on
        print(f"scopes: no op map: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return {}
    names = op_names(text)
    print(f"scopes: op map of {len(names)} instructions in "
          f"{time.perf_counter() - t0:.2f} s", file=sys.stderr, flush=True)
    return names


def reading(ctx) -> Optional[dict]:
    """Per-step ms by layer and by phase for the traced window, computed
    once per run and kept in ``ctx.layer``; None without a trace."""
    layer = ctx.layer
    tr = layer.get("trace")
    if not tr or not tr["devices"] or not layer.get("steps"):
        return None
    if "scopes" not in layer:
        if "op_names" not in tr:
            tr["op_names"] = step_op_names(ctx)
        got = attribute(tr, tr["op_names"])
        busy = trace.mean_busy_s(tr) * 1e9
        if busy > 0:
            print(f"scopes: {100.0 * got['missing_ns'] / busy:.3f}% of "
                  "device busy time in ops missing from the op map",
                  file=sys.stderr, flush=True)
        per = 1e6 * layer["steps"]
        layer["scopes"] = {
            "layer": {k: v / per for k, v in got["layer"].items()},
            "phase": {k: v / per for k, v in got["phase"].items()}}
    return layer["scopes"]


def layer_ms(ctx, scope: str) -> Optional[float]:
    """Device ms a step in one layer, all phases; None if none ran."""
    r = reading(ctx)
    return None if r is None else r["layer"].get(scope)


def phase_ms(ctx, phase: str) -> Optional[float]:
    """Device ms a step in one phase, all layers; None if none ran."""
    r = reading(ctx)
    return None if r is None else r["phase"].get(phase)
