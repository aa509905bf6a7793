"""Share of the traced window in which a pipeline stage's devices compute
nothing, in percent: no op runs, or only collectives (waits on the stage
and tensor rings and on the host; the schedule's fill and drain ticks
compute on masked inputs and count as computing).  The mean over the
stage's devices, the larger of the stages (bench/rings.py)."""
from bench import rings


def read(ctx):
    return rings.stage_idle_pct(ctx)
