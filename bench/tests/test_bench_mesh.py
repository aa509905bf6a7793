"""The mesh cell's check on the CPU: the program's stage=2 x tensor=2 step
(bench/drivers/train_mesh.py) on four host devices against the mesh-aware
reference (bench/reference/dense_lm_mesh.py), at a test size with
StarCoder2's layout (GQA, tied head), each scenario in its own process
(bench/tests/meshcell.py)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCENARIOS = ("cell", "placement", "plain")

# Limits, each between what the right comparison reads and what a wrong
# one does (seed 7; the readings in brackets):
LIMITS = {
    # q8 on both rings, the program in bf16 as it runs: its rounding
    # against a float32 reference [loss 1.6e-5, grad 0.0014, update
    # 0.0086]; the float8 control reads update 0.35
    "cell": {"loss_gap": 1e-3, "grad_gap": 0.01, "update_gap": 0.03},
    # q4 on both rings with the program at float32, so that the codec's
    # error dwarfs the program's rounding [loss 1.5e-4, grad 0.005,
    # update 0.0039]; leaving the codec off the stage ring reads loss
    # 6.3e-4 and grad 0.075, off the tensor ring 1.5e-3 and 0.12, and
    # the float8 control 1.0e-3, 0.067 and 0.35.  (At q8 a missing ring
    # moves per-leaf norms less than bf16 rounding does.)
    "placement": {"loss_gap": 3e-4, "grad_gap": 0.02, "update_gap": 0.03},
}


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    procs = {w: subprocess.Popen(
        [sys.executable, "-m", "bench.tests.meshcell", w], cwd=ROOT,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for w in SCENARIOS}
    out = {}
    for w, p in procs.items():
        stdout, stderr = p.communicate(timeout=600)
        assert p.returncode == 0, stderr[-3000:]
        out[w] = json.loads(stdout.strip().splitlines()[-1])
    return out


def _within(gaps, limits):
    return all(gaps[k] <= v for k, v in limits.items())


@pytest.mark.parametrize("scenario", sorted(LIMITS))
def test_program_is_within_the_limits(readings, scenario):
    gaps = readings[scenario]["program"]
    assert _within(gaps, LIMITS[scenario]), gaps


@pytest.mark.parametrize("scenario", sorted(LIMITS))
def test_float8_control_is_not(readings, scenario):
    gaps = readings[scenario]["control"]
    assert not _within(gaps, LIMITS[scenario]), gaps


@pytest.mark.parametrize("ring", ["stage_ring_off", "tensor_ring_off"])
def test_reference_with_a_ring_codec_left_off_is_not(readings, ring):
    gaps = readings["placement"][ring]
    assert not _within(gaps, LIMITS["placement"]), gaps


def test_uncompressed_mesh_reference_is_dense_lm(readings):
    got = readings["plain"]
    assert got["same_leaves"]
    for k in ("loss_gap", "grad_gap", "update_gap"):
        assert got["mesh_vs_dense_lm"][k] < 1e-5, got


def test_ring_counter_matches_the_compiled_step(readings):
    """``mesh_wire_bytes`` against the collectives of the compiled step:
    the layer body holds one tensor-ring hop per counted hop of a layer
    and tick (tp = 2: one hop a collective); the stage ring sends each of
    the q8 payload's three leaves (codes, min, scale on the CPU's jnp
    route) once a tick each way."""
    got, wire = readings["cell"]["ring_instructions"], \
        readings["cell"]["mesh_wire"]
    ticks, layers_per_stage = 3, 1
    assert wire["tensor"]["hops"] == ticks * layers_per_stage * got["tensor"]
    assert got["stage"] == 3 * wire["stage"]["hops"] // ticks
    assert wire["stage"]["payload_bytes_per_hop"] == \
        wire["stage"]["elems_per_hop"] + 8
