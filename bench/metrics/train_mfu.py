"""Whole-step model FLOP utilisation of a training cell, in percent: the
model FLOPs of the traced window's steps (bench/flops.py, PaLM's count,
recomputation not counted) over the traced window's length times the
chips times the chip's bf16 peak (bench/peaks.json)."""


def read(ctx):
    layer = ctx.layer
    if "model_flops" not in layer or layer["window_s"] <= 0:
        return None
    return 100.0 * layer["model_flops"] / (
        layer["window_s"] * len(ctx.devices) * ctx.peak["bf16_flops"])
