"""A test-sized mesh cell (bench/tests/data/tiny.json over stage=2 x
tensor=2) run on four host devices: the program's first steps through
bench/drivers/train_mesh.py against bench/reference/dense_lm_mesh.py.

Run as a script in its own process, since the host device count is fixed
when JAX starts:

    python -m bench.tests.meshcell cell        # q8 on both rings, bf16
    python -m bench.tests.meshcell placement   # q4, the program in f32
    python -m bench.tests.meshcell plain       # every wire none

Prints one JSON object of gaps (train_sim.gaps) keyed by what was
compared.  ``placement`` runs the program at float32 (the model's DTYPE
and its weights), so that its own rounding sits far below the codec's
and a reference that leaves a ring's codec off shows.
"""
import json
import os
import sys
import time

SEED = 7


def _ctx(harness, tinycell, jax, wire):
    cell = dict(tinycell.load("tiny-q4q8"), driver="train_mesh",
                policy={"mesh": "stage=2,tensor=2", "wire": wire,
                        "microbatches": 2},
                check_steps=2)
    conf = dict(tinycell.load("tiny"), reference="dense_lm_mesh")
    # 2 microbatches of 8 rows, as the chip cell's
    mix = dict(tinycell.load("tiny-mix"), batch=16)
    return harness.Ctx(
        cell_name="tiny-mesh", cell=cell, conf_name="tiny", conf=conf,
        mix=mix, seed=SEED, seconds=0.2, trace=False,
        devices=jax.devices()[:4],
        peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        t0=time.perf_counter())


def main(what: str) -> dict:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    if what == "placement":
        import repro.models.common as common
        common.DTYPE = jnp.float32
    from bench import harness
    from bench.reference import dense_lm, dense_lm_mesh
    from bench.tests import tinycell

    wire = {"cell": "stage=q8,tensor=q8", "placement": "stage=q4,tensor=q4",
            "plain": "stage=none,tensor=none"}[what]
    ctx = _ctx(harness, tinycell, jax, wire)
    drv = harness.driver("train_mesh")
    n = ctx.cell["check_steps"]
    pool = None

    def ref(wire=wire, precision="f32"):
        return dense_lm_mesh.train_readings(
            ctx.conf, dict(ctx.cell["policy"], wire=wire),
            ctx.cell["optimizer"], list(pool[:n]), ctx.seed, precision)

    if what == "plain":
        from bench import traffic
        pool = traffic.markov2_pool(ctx.mix, 512, 16, ctx.mix["seq"],
                                    ctx.seed)
        flat = dense_lm.train_readings(
            ctx.conf, {"num_stages": 1, "fw": ["none"], "bw": ["none"]},
            ctx.cell["optimizer"], list(pool[:n]), ctx.seed)
        mesh = ref()
        return {"mesh_vs_dense_lm": drv.gaps(mesh, flat),
                "same_leaves": mesh["leaves"] == flat["leaves"]}
    if what == "placement":
        made = drv.sharded_weights
        drv.sharded_weights = lambda *a: jax.tree.map(
            lambda x: x.astype(jnp.float32), made(*a))
    step, state, feed, ids, pool = drv.build(ctx)
    prog = drv.first_steps(ctx, drv.stepper(step, state, feed, ids), state,
                           n)
    state.clear()
    sound = ref()
    out = {"program": drv.gaps(prog, sound),
           "control": drv.gaps(ref(precision="fp8"), sound)}
    if what == "cell":
        from bench import rings, scopes
        from bench.configs import model_config
        from repro.train.steps import mesh_wire_bytes
        names = scopes.step_op_names(ctx)
        out["ring_instructions"] = {
            ring: len(rings.ring_instructions(names, f"{ring}_wire"))
            for ring in ("stage", "tensor")}
        out["mesh_wire"] = mesh_wire_bytes(
            model_config(ctx.conf, "tiny"),
            drv.parallel_spec(ctx.cell["policy"]), ctx.mix["batch"],
            ctx.mix["seq"], microbatches=2)
    if what == "placement":
        out["stage_ring_off"] = drv.gaps(prog, ref("stage=none,tensor=q4"))
        out["tensor_ring_off"] = drv.gaps(prog, ref("stage=q4,tensor=none"))
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1])))
