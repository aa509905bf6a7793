#!/usr/bin/env python3
"""Chip smoke test: gpt2-small at its published widths on a TPU.

    python chip_smoke.py             # one chip: wire, train and serve
    python chip_smoke.py --chips 4   # four chips: the 2 stage x 2 tensor
                                     # mesh against its one-chip reference

Everything runs in this one process, which holds the chip.  Each phase
prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
No phase's error is caught: any failed check exits non-zero before that
line.  Without a TPU the script exits non-zero and prints no result.
Weights are random (``--seed``); compile and step seconds are what one run
printed, not a benchmark.

Phases on one chip:

* ``wire``  — the q8, q4 and TopK wire codecs at the training boundary
  (8 examples x 1024 tokens x 768 features): the route each takes, and
  the Pallas payloads checked against the jnp codecs on the chip.
* ``train`` — ``make_lm_train_step`` (the trainer of
  ``repro.launch.train``) on full-width gpt2-small, simulated transport,
  ``--policy q4q8 --seq 1024 --batch 8``, 3 steps: every loss finite, the
  last below the first, and ``tpu_custom_call`` in the step program.
* ``serve`` — ``ContinuousEngine`` with ``--policy top10`` wire codecs:
  8 requests over 4 slots, prompts up to 128 tokens, 32 greedy tokens
  each: all complete, token ids below the vocab size, and request 0 served
  alone reproduces its batched tokens.

With ``--chips 4`` only the model-parallel path runs: ``--mesh
stage=2,tensor=2`` for 3 steps with ``--wire stage=none,tensor=none`` and
3 with ``stage=q8,tensor=q8`` from the same seed and batch.  The ``none``
first-step loss must match the one-chip simulated loss within bf16
tolerance, the q8 losses must be finite, and every device must hold at
least its share of the parameters.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "gpt2-small"
SEQ = 1024
# loss tolerance of the mesh against one chip: two bf16 ulps, relative
BF16_RTOL = 2.0 ** -7


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# wire: the codec kernels against the jnp codecs, on the chip
# ---------------------------------------------------------------------------

def wire_phase(seed: int, kind: str) -> None:
    import repro.core.compressors as C
    from repro.configs.registry import get
    from repro.transport.codecs import get_codec, wire_route
    from repro.kernels.framing import frame_parts, unframe_parts

    cfg = get(ARCH)
    shape = (8, SEQ * cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)

    def both(name, k_frac=0.1):
        routes, out = {}, {}
        for backend in ("auto", "jnp"):
            C.KERNEL_BACKEND = backend
            try:
                codec = get_codec(name)
                routes[backend] = wire_route(name, shape)
                pack = jax.jit(lambda a: codec.pack(a, k_frac))
                payload, sec = timed(pack, x)
                dense = jax.jit(lambda p: codec.unpack(p, shape,
                                                       jnp.float32))(payload)
                out[backend] = (payload, np.asarray(dense), sec)
            finally:
                C.KERNEL_BACKEND = "auto"
        return routes["auto"], out["auto"], out["jnp"]

    rec = {"phase": "wire", "device_kind": kind, "shape": list(shape)}
    xs = np.asarray(x)

    route, (pk, dk, sk), (pj, dj, _) = both("q4")
    check(route == "pallas", f"q4 wire route {route}")
    for key in pj:
        check(np.array_equal(np.asarray(pk[key]), np.asarray(pj[key])),
              f"q4 {key} differs from the jnp wire bytes")
    err = float(np.abs(dk - dj).max())
    check(err <= 1.2e-7 * max(float(np.abs(dj).max()), 1.0),
          f"q4 unpack differs from jnp by {err}")
    rec["q4"] = {"route": route, "bytes_equal": True, "unpack_max_diff": err,
                 "first_pack_s": sk}

    route, (pk, dk, sk), (pj, _, _) = both("q8")
    check(route == "pallas" and "tile_meta" in pk, f"q8 wire route {route}")
    bound = 0.5 * float(pj["scale"]) * 1.001        # per-tensor step / 2
    err = float(np.abs(dk - xs).max())
    check(err <= bound, f"q8 round trip error {err} > {bound}")
    rec["q8"] = {"route": route, "roundtrip_max_err": err,
                 "per_tensor_bound": bound, "first_pack_s": sk}

    route, (pk, dk, sk), (pj, dj, _) = both("topk")
    check(route == "pallas", f"topk wire route {route}")
    ik, ij = np.asarray(pk["idx"]), np.asarray(pj["idx"])
    for r in range(shape[0]):
        check(set(ik[r].tolist()) == set(ij[r].tolist()),
              f"topk row {r}: index set differs from lax.top_k")
    check(np.array_equal(dk, dj), "topk dense scatter differs")
    rec["topk"] = {"route": route, "index_sets_equal": True,
                   "first_pack_s": sk}

    q4 = get_codec("q4").pack(x)                         # the framed hop
    parts = [jax.lax.bitcast_convert_type(a, jnp.uint8).reshape(-1)
             for a in jax.tree.leaves(q4)]
    buf = jax.jit(lambda ps: frame_parts(ps))(parts)
    check(np.array_equal(np.asarray(buf),
                         np.asarray(jnp.concatenate(parts))),
          "frame_parts differs from concatenate")
    back = jax.jit(lambda b: unframe_parts(b, [p.size for p in parts]))(buf)
    check(all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(back, parts)), "unframe_parts round trip")
    rec["framing"] = {"bytes": int(buf.size), "byte_identical": True}
    emit(rec)


# ---------------------------------------------------------------------------
# train: the launch/train.py trainer at full width
# ---------------------------------------------------------------------------

def train_run(cfg, policy, *, batch: int, steps: int, seed: int,
              parallel=None, lr: float = 1e-3) -> dict:
    """``steps`` steps of the trainer; returns losses, times and the
    compiled step's kernel count."""
    from repro.launch.train import (adamw_config, init_bstates, make_batch,
                                    synthetic_stream)
    from repro.models import transformer
    from repro.optim.optimizers import init_opt_state
    from repro.train.steps import _resolve_parallel, make_lm_train_step

    policy_eff, transport_eff = policy, "simulated"
    if parallel is not None:
        _, policy_eff, transport_eff = _resolve_parallel(
            "chip_smoke", parallel, policy, "simulated", {})
    opt = adamw_config(lr, steps)
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    opt_state = init_opt_state(opt, params)
    bstates = init_bstates(cfg, policy_eff, transport_eff, seq=SEQ,
                           batch=batch)
    kw = {"parallel": parallel} if parallel is not None else {}
    step_fn = make_lm_train_step(cfg, policy, opt, remat=True, donate=False,
                                 **kw)
    stream = synthetic_stream(cfg, batch, SEQ, seed)
    toks, ids = next(stream)
    args = (params, opt_state, bstates, make_batch(cfg, toks),
            jnp.asarray(ids))
    t0 = time.perf_counter()
    compiled = step_fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    losses, step_s = [], []
    for i in range(steps):
        if i:
            toks, ids = next(stream)
            args = (params, opt_state, bstates, make_batch(cfg, toks),
                    jnp.asarray(ids))
        out, sec = timed(compiled, *args)
        params, opt_state, bstates, m = out[0], out[1], out[2], out[-1]
        losses.append(float(m["loss"]))
        step_s.append(sec)
    return {"transport": transport_eff, "compile_s": compile_s,
            "step_s": step_s, "losses": losses,
            "tpu_custom_calls": kernels, "params": params}


def train_phase(seed: int, kind: str) -> None:
    from repro.configs.registry import get
    from repro.launch.train import POLICIES

    cfg = get(ARCH)
    r = train_run(cfg, POLICIES["q4q8"](), batch=8, steps=3, seed=seed)
    r.pop("params")
    emit({"phase": "train", "device_kind": kind, "arch": cfg.arch_id,
          "layers": cfg.num_layers, "d_model": cfg.d_model,
          "vocab": cfg.vocab_size, "policy": "q4q8", "batch": 8, "seq": SEQ,
          **r})
    check(all(math.isfinite(v) for v in r["losses"]),
          f"non-finite loss {r['losses']}")
    check(r["losses"][-1] < r["losses"][0],
          f"loss did not fall over 3 steps: {r['losses']}")
    check(r["tpu_custom_calls"] > 0, "no tpu_custom_call in the train step")


# ---------------------------------------------------------------------------
# serve: ContinuousEngine with the top10 wire codecs
# ---------------------------------------------------------------------------

def serve_phase(seed: int, kind: str) -> None:
    from repro.configs.registry import get
    from repro.launch.train import POLICIES
    from repro.models import transformer
    from repro.serve.engine import ContinuousEngine
    from repro.transport.codecs import wire_route

    cfg = get(ARCH)
    policy = POLICIES["top10"]()
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    engine = ContinuousEngine(params, cfg, policy, compress=True,
                              num_slots=4, max_seq=256, max_prompt=128)
    t0 = time.perf_counter()
    compiles = engine.warmup()
    warm_s = time.perf_counter() - t0
    rng = np.random.RandomState(seed)
    lens = rng.randint(1, 129, 8)
    lens[0] = 128
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    new = 32
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=new, seed=seed + i)
    done = sorted(engine.drain(), key=lambda r: r.req_id)
    wall = time.perf_counter() - t0
    tokens = [np.asarray(r.out) for r in done]
    engine.submit(prompts[0], max_new_tokens=new, seed=seed)
    solo = np.asarray(engine.drain()[0].out)
    emit({"phase": "serve", "device_kind": kind, "policy": "top10",
          "wire_route": {"stage": wire_route("topk", (1, cfg.d_model))},
          "warmup_s": warm_s, "compiles": compiles, "drain_s": wall,
          "requests": len(done), "prompt_lens": lens.tolist(),
          "new_tokens": [len(t) for t in tokens],
          "first_tokens": tokens[0][:8].tolist(),
          "solo_matches_batched": bool(np.array_equal(solo, tokens[0]))})
    check(len(done) == 8, f"{len(done)} of 8 requests completed")
    check(all(len(t) == new for t in tokens), "a request stopped early")
    check(all(((t >= 0) & (t < cfg.vocab_size)).all() for t in tokens),
          "a token id outside the vocabulary")
    check(np.array_equal(solo, tokens[0]),
          "request 0 alone differs from its batched tokens")


# ---------------------------------------------------------------------------
# --chips 4: stage=2 x tensor=2 against one chip
# ---------------------------------------------------------------------------

def mesh_phase(seed: int, kind: str) -> None:
    from repro.configs.registry import get
    from repro.core.parallel import spec_from_cli
    from repro.core.policy import NO_POLICY
    from repro.models.config import param_count
    from repro.transport.codecs import wire_route

    cfg = get(ARCH)
    batch = 16          # 2 pipeline microbatches of 8 rows: a q8 tiling
    ref = train_run(cfg, NO_POLICY, batch=batch, steps=1, seed=seed)
    ref.pop("params")
    emit({"phase": "reference", "device_kind": kind, "devices": 1,
          "batch": batch, "seq": SEQ, **ref})
    fair = None
    runs = {}
    for wire in ("none", "q8"):
        spec = spec_from_cli("stage=2,tensor=2",
                             f"stage={wire},tensor={wire}")
        spec = spec.resolved({"data": param_count(cfg),
                              "stage": SEQ * cfg.d_model,
                              "tensor": SEQ * cfg.d_model // spec.tp})
        r = train_run(cfg, NO_POLICY, batch=batch, steps=3, seed=seed,
                      parallel=spec)
        params = r.pop("params")
        param_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
        fair = param_bytes / len(jax.devices())
        in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        del params
        # stage wire: one microbatch's sequence shard; tensor wire: the
        # (1, n) activation shard each TP hop packs
        mb_rows = batch // spec.stages
        routes = {"stage": wire_route(wire, (mb_rows,
                                             SEQ // spec.tp * cfg.d_model)),
                  "tensor": wire_route(wire, (1, mb_rows * SEQ // spec.tp
                                              * cfg.d_model))}
        runs[wire] = r
        emit({"phase": "mesh", "device_kind": kind, "mesh": "stage=2,tensor=2",
              "wire": wire, "batch": batch, "seq": SEQ, "routes": routes,
              "bytes_in_use": in_use, "param_bytes": param_bytes, **r})
        check(all(b >= fair for b in in_use),
              f"a device holds less than its share {fair} of the "
              f"parameters: {in_use}")
    none0, ref0 = runs["none"]["losses"][0], ref["losses"][0]
    emit({"phase": "compare", "none_first_loss": none0,
          "one_chip_first_loss": ref0, "rel_diff": abs(none0 - ref0) / ref0,
          "rtol": BF16_RTOL})
    check(abs(none0 - ref0) <= BF16_RTOL * abs(ref0),
          f"mesh loss {none0} vs one chip {ref0}")
    check(all(math.isfinite(v) for v in runs["q8"]["losses"]),
          f"non-finite q8 loss {runs['q8']['losses']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    kind = dev.device_kind
    if args.chips == 4:
        mesh_phase(args.seed, kind)
    else:
        wire_phase(args.seed, kind)
        train_phase(args.seed, kind)
        serve_phase(args.seed, kind)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
