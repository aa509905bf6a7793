"""A test-sized training cell (bench/tests/data/) run through the harness
on the CPU, with the Pallas codec kernels in interpret mode so that the
program's cuts compute what they compute on the TPU."""
import json
import os
import time

import jax

from bench import harness

DATA = os.path.join(os.path.dirname(__file__), "data")


def load(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def ctx(cell_name="tiny-q4q8", seed=1, seconds=0.2, trace=False):
    cell = load(cell_name)
    return harness.Ctx(
        cell_name=cell_name, cell=cell, conf_name="tiny", conf=load("tiny"),
        mix=load("tiny-mix"), seed=seed, seconds=seconds, trace=trace,
        devices=jax.devices()[:1],
        peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        t0=time.perf_counter())


MANIFEST = {
    "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                   {"name": "peak_hbm_gib", "unit": "GiB"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": "train_mfu", "unit": "%",
                   "moves": "train_tokens_per_s"}],
}

