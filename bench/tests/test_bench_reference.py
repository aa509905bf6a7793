"""The plain reference (bench/reference/) against the program's train step
at a test size on the CPU, for the two codec mixes the cells run."""
import pytest

import repro.core.compressors as C
from bench import harness
from bench.tests import tinycell

# test-size gaps seen on the CPU (bf16 program against the float32
# reference) are a few 1e-3 on the loss, under 0.03 on the gradient
# norms and under 0.01 on the change; TopK's selection makes its cell
# noisier than quantization's
TOLERANCE = {
    "tiny-top10": {"loss_gap": 0.01, "grad_gap": 0.1, "update_gap": 0.05},
    "tiny-q4q8": {"loss_gap": 0.005, "grad_gap": 0.02, "update_gap": 0.02},
}


@pytest.mark.parametrize("cell", sorted(TOLERANCE))
def test_program_step_matches_the_reference(monkeypatch, cell):
    monkeypatch.setattr(C, "KERNEL_BACKEND", "pallas")
    ctx = tinycell.ctx(cell)
    drv = harness.driver("train_sim")
    step, state, feed, ids, pool = drv.build(ctx)
    prog = drv.first_steps(ctx, drv.stepper(step, state, feed, ids), state,
                           ctx.cell["check_steps"])
    ref = drv.reference(ctx, pool[:ctx.cell["check_steps"]])
    assert len(ref["leaves"]) == len(prog["grad_norms"])
    gaps = drv.gaps(prog, ref)
    for name, limit in TOLERANCE[cell].items():
        assert gaps[name] <= limit, (name, gaps)
