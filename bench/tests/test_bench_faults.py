"""A whole run of a test-size training cell through the harness, sound and
with the timed path broken underneath: each fault and the control must
come out ``correct: false``."""
import jax.numpy as jnp
import pytest

import repro.core.compressors as C
import repro.train.steps as steps
from bench import calibrate, harness
from bench.tests import tinycell


@pytest.fixture(autouse=True)
def _kernels(monkeypatch):
    monkeypatch.setattr(C, "KERNEL_BACKEND", "pallas")
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda devices: 1)


def _run():
    line = harness.run_cell(tinycell.ctx(), tinycell.MANIFEST)
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "update_gap"}
    return line


def _broken(monkeypatch, wrap):
    real = steps.make_lm_train_step

    def make(*a, **kw):
        kw["donate"] = False
        return wrap(real(*a, **kw))
    monkeypatch.setattr(steps, "make_lm_train_step", make)


def test_sound_run_is_correct():
    line = _run()
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_state_left_unchanged_is_not_correct(monkeypatch):
    def wrap(step):
        def unchanged(p, o, b, batch, ids):
            return (p, o, b, step(p, o, b, batch, ids)[3])
        return unchanged
    _broken(monkeypatch, wrap)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_is_not_correct(monkeypatch):
    def wrap(step):
        def half(p, o, b, batch, ids):
            n = batch["tokens"].shape[0] // 2
            return step(p, o, b, {"tokens": batch["tokens"][:n]}, ids[:n])
        return half
    _broken(monkeypatch, wrap)
    assert not _run()["correct"]


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    ctx = tinycell.ctx()
    calibrate.control_in_place(harness.driver("train_sim"), ctx,
                               monkeypatch.setattr)
    line = harness.run_cell(ctx, tinycell.MANIFEST)
    assert not line["correct"]
    assert not line["checks"]["update_gap"]["value"] <= \
        line["checks"]["update_gap"]["limit"]


def test_a_nan_step_in_the_window_is_counted_failed(monkeypatch):
    def wrap(step):
        calls = []

        def nan_later(p, o, b, batch, ids):
            out = step(p, o, b, batch, ids)
            calls.append(1)
            if len(calls) > 3:
                return out[:3] + ({"loss": jnp.float32(jnp.nan)},)
            return out
        return nan_later
    _broken(monkeypatch, wrap)
    line = _run()
    assert not line["correct"] and line["failed"] > 0
