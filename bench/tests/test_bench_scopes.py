"""Device time by the program's named scopes (bench/scopes.py): the scopes
in the compiled step, the attribution on hand-made ops, and a crop of a
trace recorded on the chip (bench/tests/data/)."""
import json
import os

import pytest

from bench import harness, scopes, trace
from bench.tests import tinycell

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "scopes-gpt2s-train-top10.json")


@pytest.fixture(scope="module")
def tiny_op_names():
    ctx = tinycell.ctx("tiny-top10")
    return scopes.step_op_names(ctx)


def test_every_scope_is_in_the_compiled_step(tiny_op_names):
    names = [n for n in tiny_op_names.values() if n]
    assert len(names) > 100
    assert {scopes.layer_of(n) for n in names} >= set(scopes.SCOPES)
    comps = {c for n in names for c in n.split("/")}
    assert "jvp(codec)" in comps and "transpose(jvp(codec))" in comps
    assert any(scopes.layer_of(n) == "attn" and "rematted_computation" in n
               for n in names)
    phases = {scopes.phase_of(n) for n in names}
    assert phases >= set(scopes.PHASES)


@pytest.mark.parametrize("op_name,layer,phase", [
    ("jit(step)/jvp(layers)/while/body/closed_call/attn/dot_general",
     "attn", "forward"),
    # the innermost scope wins over the one around it
    ("jit(step)/jvp(vocab)/vocab/norm/mul", "norm", "forward"),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "mlp/dot_general", "mlp", "backward"),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/exp", "attn", "recompute"),
    ("jit(step)/transpose(jvp(codec))/pallas_call", "codec", "backward"),
    ("jit(step)/jvp(codec)/top_k", "codec", "forward"),
    ("jit(step)/optimizer/jit(clip)/min", "optimizer", "optimizer"),
    # a whole component, never a substring or a jitted function's name
    ("jit(step)/jvp(layers)/while/body/jit(norm)/add", "layers", "forward"),
    ("jit(step)/attention/mlpx/dot_general", "unscoped", "forward"),
    ("jit(step)/jvp()/add", "unscoped", "forward"),
    ("", "unscoped", "forward"),
])
def test_layer_and_phase_of_an_op_name(op_name, layer, phase):
    assert scopes.layer_of(op_name) == layer
    assert scopes.phase_of(op_name) == phase


def test_op_names_from_compiled_text():
    text = "\n".join([
        "ENTRY %main.1 (p: f32[4]) -> f32[4] {",
        '  %fusion.922 = bf16[8,128]{1,0} fusion(%p), kind=kLoop, '
        'calls=%fc.1, metadata={op_name="jit(step)/jvp(layers)/attn/mul" '
        'source_file="x.py" source_line=3}',
        "  %copy-start.31 = (bf16[8]{0}, bf16[8]{0}) copy-start(%p)",
        '  ROOT %tuple.7 = (f32[4]) tuple(%fusion.922), '
        'metadata={op_name="jit(step)/optimizer/add"}',
        "}"])
    assert scopes.op_names(text) == {
        "fusion.922": "jit(step)/jvp(layers)/attn/mul",
        "copy-start.31": "", "tuple.7": "jit(step)/optimizer/add"}


def _tr():
    # window 0..100 ns on two devices.  Device 0: a while loop 0..60 of
    # the layers scan holding an attention op 10..30 and a recomputed MLP
    # op 30..50; a backward codec op 60..70; an op 70..80 whose
    # instruction the map lacks; the optimizer 80..100.  Device 1: one
    # norm op 0..40.
    return {"window": [0.0, 100.0],
            "devices": {
                "0": [["%while.1 = (f32[2])", 0, 60],
                      ["%fusion.2 = bf16[8]", 10, 20],
                      ["%fusion.3 = bf16[8]", 30, 20],
                      ["%custom-call.4 = bf16[8]", 60, 10],
                      ["%copy.5 = bf16[8]", 70, 10],
                      ["%fusion.6 = f32[8]", 80, 20]],
                "1": [["%fusion.7 = bf16[8]", 0, 40]]},
            "host": [["bench.window", 0, 100]]}


NAMES = {
    "while.1": "jit(step)/jvp(layers)/while",
    "fusion.2": "jit(step)/jvp(layers)/while/body/attn/dot_general",
    "fusion.3": "jit(step)/transpose(jvp(layers))/while/body/checkpoint/"
                "rematted_computation/mlp/dot_general",
    "custom-call.4": "jit(step)/transpose(jvp(codec))/pallas_call",
    "fusion.6": "jit(step)/optimizer/sqrt",
    "fusion.7": "jit(step)/jvp(layers)/while/body/norm/mul",
}


def test_attribution_by_layer_and_phase():
    got = scopes.attribute(_tr(), NAMES)
    # self time: the while loop keeps 20 of its 60 ns; means over 2 devices
    assert got["layer"] == pytest.approx({
        "layers": 20 / 2, "attn": 20 / 2, "mlp": 20 / 2, "codec": 10 / 2,
        "unscoped": 10 / 2, "optimizer": 20 / 2, "norm": 40 / 2})
    assert got["phase"] == pytest.approx({
        "forward": (20 + 20 + 10 + 40) / 2, "recompute": 20 / 2,
        "backward": 10 / 2, "optimizer": 20 / 2})
    assert got["missing_ns"] == pytest.approx(10 / 2)
    busy = trace.mean_busy_s(_tr()) * 1e9
    assert sum(got["layer"].values()) == pytest.approx(busy)
    assert sum(got["phase"].values()) == pytest.approx(busy)


class _Ctx:
    def __init__(self, tr, steps):
        self.layer = {"trace": tr, "steps": steps}


def test_readers_per_step_and_none_without_scopes():
    tr = dict(_tr(), op_names=NAMES)
    ctx = _Ctx(tr, steps=2)
    read = {m: harness.metric_reader(m) for m in (
        "attn_ms.train", "mlp_ms.train", "vocab_ms.train", "codec_ms.train",
        "optimizer_ms.train", "backward_ms.train", "recompute_ms.train")}
    got = {m: r(ctx) for m, r in read.items()}
    assert got == pytest.approx({
        "attn_ms.train": 10 / 2 / 1e6, "mlp_ms.train": 10 / 2 / 1e6,
        "vocab_ms.train": None, "codec_ms.train": 5 / 2 / 1e6,
        "optimizer_ms.train": 10 / 2 / 1e6,
        "backward_ms.train": 5 / 2 / 1e6,
        "recompute_ms.train": 10 / 2 / 1e6})
    # a program without the scopes: the same ops, op_names of no scope
    bare = {k: "jit(step)/while/body/dot_general" for k in NAMES}
    ctx = _Ctx(dict(_tr(), op_names=bare), steps=2)
    assert all(r(ctx) is None for r in read.values())
    assert read["attn_ms.train"](_Ctx({}, steps=2)) is None


def _recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_crop_reduces_to_its_recorded_numbers():
    rec = _recorded()
    tr, want = rec["trace"], rec["reduced"]
    got = scopes.attribute(tr, tr["op_names"])
    assert got["layer"] == pytest.approx(want["layer_ns"])
    assert got["phase"] == pytest.approx(want["phase_ns"])
    assert got["missing_ns"] == pytest.approx(want["missing_ns"])
    busy = trace.mean_busy_s(tr) * 1e9
    assert busy == pytest.approx(want["busy_ns"])
    assert sum(got["layer"].values()) == pytest.approx(busy, rel=0.01)
    # the crop holds a codec kernel, and the layers and phases around it
    assert {"codec", "attn", "mlp", "norm"} <= set(got["layer"])
    assert {"backward", "recompute"} <= set(got["phase"])
