#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload gpt2s-train-top10 --seed 7 \
        --seconds 10 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device`` and, last,
``checks``: each number the correctness check compared, with its limit.
The same numbers end standard error.  Without a TPU, with fewer chips
than the cell asks for, or on a device kind that bench/peaks.json does
not list, the run exits 1 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the compile cache lives at one fixed place inside the checkout, whatever
# the machine's environment says, so that only a cell's first run compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


NOT_FINITE = 1e30


def finite(x):
    """The result line is strict JSON: a value that is not finite (a check
    on a step whose loss was NaN) prints as 1e30."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return -NOT_FINITE if x < 0 else NOT_FINITE
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    try:
        man = harness.manifest()
        entry = harness.cell_entry(man, args.workload)
        cell = harness.cell_file(args.workload)
        conf = harness.config_file(cell["config"])
        mix = harness.mix_file(cell["traffic"])
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        devices, peak = harness.find_devices(entry["chips"])
    except (harness.BenchError, OSError, KeyError, ImportError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    ctx = harness.Ctx(cell_name=args.workload, cell=cell,
                      conf_name=cell["config"], conf=conf, mix=mix,
                      seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, peak=peak, t0=T0,
                      trace_dir=os.path.join(ROOT, "bench_out", "trace"))
    line = harness.run_cell(ctx, man)
    harness.print_checks(line["checks"])
    print(json.dumps(finite(line), allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
