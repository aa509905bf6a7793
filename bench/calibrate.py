#!/usr/bin/env python3
"""Readings that the correctness limits of a training cell are set from.

    python bench/calibrate.py --workload gpt2s-train-top10 \
        --seeds 101,102,103 --what program,half,control

For each seed, in one process on the chip, at the cell's own size:

* ``program`` — the timed step's first steps against the plain reference
  (the lower readings);
* ``control`` — a whole run of the cell through the harness, with a
  ``--seconds`` window at the cell's own load, whose comparison reads the
  reference computed in float8 (weights kept in e4m3, matmul operands in
  e4m3, gradients in e5m2: the step below the program's bf16) in the
  program's place (the upper readings): prints the run's ``correct`` and
  each number beside its limit;
* ``half``    — the fault "half of the batch left out, the mean taken
  over the rest": the timed step fed the first half of each batch's rows
  (of its positions, for a batch of one row), against the reference on
  the whole batch.

A state left unchanged reads 1 on ``update_gap`` by definition and needs
no run.  Prints one JSON line per seed and reading.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control_in_place(drv, ctx, setattr=setattr):
    """Make the driver's comparison read the float8 control in the
    program's place: once the window has closed, the control follows the
    same first steps as the reference, and its readings are compared with
    the reference's under the cell's limits."""
    from bench import traffic
    from bench.configs import sizes
    real_gaps, n = drv.gaps, int(ctx.cell["check_steps"])

    def gaps(prog, ref):
        pool = traffic.markov2_pool(ctx.mix, sizes(ctx.conf)["vocab_size"],
                                    ctx.mix["batch"], ctx.mix["seq"],
                                    ctx.seed)
        return real_gaps(drv.reference(ctx, pool[:n], "fp8"), ref)
    setattr(drv, "gaps", gaps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,half,control")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    man = harness.manifest()
    entry = harness.cell_entry(man, args.workload)
    devices, peak = harness.find_devices(entry["chips"])
    cell = harness.cell_file(args.workload)
    conf = harness.config_file(cell["config"])
    mix = harness.mix_file(cell["traffic"])
    drv = harness.driver(cell["driver"])
    what = args.what.split(",")
    n = int(cell["check_steps"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Ctx(cell_name=args.workload, cell=cell,
                          conf_name=cell["config"], conf=conf, mix=mix,
                          seed=seed, seconds=args.seconds, trace=False,
                          devices=devices, peak=peak, t0=time.perf_counter())
        if "control" in what:
            real_gaps = drv.gaps
            control_in_place(drv, ctx)
            line = harness.run_cell(ctx, man)
            drv.gaps = real_gaps
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": "control", **line}), flush=True)
            gc.collect()
        progs = {}
        pool = None
        for kind in [w for w in what if w in ("program", "half")]:
            step, state, feed, ids, pool = drv.build(ctx)
            if kind == "half":
                b = mix["batch"]
                if b > 1:
                    feed = [{"tokens": f["tokens"][:b // 2]} for f in feed]
                    ids = ids[:b // 2]
                else:
                    feed = [{"tokens": f["tokens"][:, :mix["seq"] // 2]}
                            for f in feed]
            progs[kind] = drv.first_steps(ctx, drv.stepper(step, state, feed,
                                                            ids), state, n)
            state.clear()
            del step, feed
            gc.collect()
        if not progs:
            continue
        t = time.perf_counter()
        ref = drv.reference(ctx, pool[:n])
        ref_s = time.perf_counter() - t
        for kind, prog in progs.items():
            rec = {"workload": args.workload, "seed": seed, "kind": kind,
                   **drv.gaps(prog, ref), "reference_s": ref_s,
                   "losses": prog["losses"], "ref_losses": ref["losses"]}
            print(json.dumps(rec), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
