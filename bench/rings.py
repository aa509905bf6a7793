"""Device time of the pipeline mesh by ring and by stage, from the
reduced trace (bench/trace.py) and the step's op map (bench/scopes.py).

The program names each ring's traffic with ``jax.named_scope``:
``stage_wire`` around a pipeline stage hop (transport/pipeline.py) and
``tensor_wire`` around a tensor-parallel gather or scatter
(transport/tp_collectives.py).  A ring's collectives are the collective
ops whose ``op_name`` path has that scope as a whole component; a ring is
*exposed* while one of its collectives runs and no op outside the ring
does (``trace.exposed_ns`` over the ring's ops).  A program without the
scopes has no ring ops, and the readers built on this give no reading
there.

The trace draws a control-flow op (the pipeline's and the layer stack's
``while`` loops) as one event around the ops of its body.  Such an event
is not work that hides a collective, so these readers leave it out;
otherwise every op inside a loop would read as covered.
"""
from __future__ import annotations

import re
from typing import Optional

from bench import scopes, trace


CONTROL_FLOW_RE = re.compile(r"^(?:while|conditional|call)(?:\.\d+)?$")


def _steps_ms(ns: float, ctx) -> float:
    return ns / 1e6 / ctx.layer["steps"]


def _traced(ctx) -> Optional[dict]:
    """The traced window without its control-flow events; None where the
    run was not traced."""
    tr = ctx.layer.get("trace")
    if not tr or not tr["devices"] or not ctx.layer.get("steps"):
        return None
    return dict(tr, devices={
        d: [op for op in ops
            if not CONTROL_FLOW_RE.match(trace.op_name(op[0]))]
        for d, ops in tr["devices"].items()})


def ring_instructions(names: dict, scope: str) -> set:
    """The collective instructions whose op_name holds ``scope``."""
    return {instr for instr, path in names.items()
            if trace.COLLECTIVE_RE.search(instr)
            and scope in (scopes._bare(c) for c in path.split("/"))}


def exposed_ms(ctx, scope: str) -> Optional[float]:
    """Exposed ms a step of one ring, the mean over the devices; None
    where the program has no collective under the scope."""
    tr = _traced(ctx)
    if tr is None:
        return None
    raw = ctx.layer["trace"]
    if "op_names" not in raw:
        raw["op_names"] = scopes.step_op_names(ctx)
    instrs = ring_instructions(raw["op_names"], scope)
    if not instrs:
        return None
    ring = re.compile("^(?:%s)$" % "|".join(map(re.escape, sorted(instrs))))
    devs = sorted(tr["devices"])
    ns = sum(trace.exposed_ns(tr, d, ring) for d in devs) / len(devs)
    return _steps_ms(ns, ctx)


def collective_exposed_ms(ctx) -> Optional[float]:
    """Exposed ms a step of every collective, the mean over the devices;
    None in a trace with no collective."""
    tr = _traced(ctx)
    if tr is None:
        return None
    devs = sorted(tr["devices"])
    if not any(trace.matching(tr, d, trace.COLLECTIVE_RE) for d in devs):
        return None
    ns = sum(trace.exposed_ns(tr, d) for d in devs) / len(devs)
    return _steps_ms(ns, ctx)


def stage_idle_pct(ctx) -> Optional[float]:
    """Share of the window in which a stage's devices compute nothing (no
    op runs, or only collectives: waits on the rings), the mean over the
    stage's devices, in percent: the larger over the stages."""
    tr = _traced(ctx)
    stages = ctx.layer.get("stage_devices")
    if tr is None or not stages:
        return None
    win = trace.window_ns(tr)

    def computing_ns(dev):
        return trace.total(trace.union(trace.clip(
            [op for op in tr["devices"][dev]
             if not trace.COLLECTIVE_RE.search(trace.op_name(op[0]))],
            tr["window"])))

    idle = [sum(1.0 - computing_ns(d) / win for d in devs) / len(devs)
            for devs in stages if all(d in tr["devices"] for d in devs)]
    return 100.0 * max(idle) if idle else None
