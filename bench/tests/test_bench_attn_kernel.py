"""attn_kernel_ms.train: the splash kernels' device time a step, found by
their names, and no reading where attention runs without them."""
import pytest

from bench import harness


class _Ctx:
    def __init__(self, tr, steps):
        self.layer = {"trace": tr, "steps": steps}


def _tr(ops):
    # window 0..100 ns; ops outside it are clipped
    return {"window": [0.0, 100.0], "devices": {"0": ops, "1": ops},
            "host": []}


def test_kernel_time_a_step():
    read = harness.metric_reader("attn_kernel_ms.train")
    ops = [["splash_mqa_fwd_residuals.1", 10, 20],
           ["%splash_mqa_dkv_no_residuals.2 = (f32[4,1024,64])", 40, 30],
           ["splash_mqa_fwd_no_residuals.3", 90, 20],
           ["fusion.4", 0, 50], ["topk_block_op.5", 75, 5]]
    # 20 + 30 + the 10 ns of the third inside the window, over 2 steps
    assert read(_Ctx(_tr(ops), steps=2)) == pytest.approx(60 / 2 / 1e6)


@pytest.mark.parametrize("tr", [
    _tr([["fusion.4", 0, 50], ["vmap_vmap_jit__splash_attention___.8", 5, 0]]),
    {"window": [0.0, 100.0], "devices": {}, "host": []},
    None])
def test_no_reading_without_the_kernels(tr):
    read = harness.metric_reader("attn_kernel_ms.train")
    assert read(_Ctx(tr, steps=2)) is None
