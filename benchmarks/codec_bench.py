"""Per-codec pack/unpack throughput: fused Pallas wire kernels vs jnp.

For every registered wire codec this measures achieved bytes/s (dense-side
bytes moved per second) of ``pack`` and ``unpack`` on both backends, plus
the payload-framing kernel (fuse/unfuse) and the fused DP decode+sum — the
whole codec hot path that PR 6 moved into Pallas.  Each row also carries a
PARITY verdict re-asserting the wire contract inline: q4 bytes bit-exact,
TopK sets equal (dense roundtrip identical), framing byte-identical, DP
decode+sum within the documented 1-ulp FMA bound.

Perf gate: on a TPU backend the Pallas path must achieve >= the jnp path's
bytes/s (asserted in-code, the ISSUE 6 acceptance).  On CPU runners the
kernels execute in INTERPRET mode — a correctness vehicle, not a perf
path (the 31-step TopK bisection in particular is slower than one XLA
sort when interpreted) — so there the ratio is recorded and banded by
``--check`` rather than asserted, and the parity booleans plus wire bytes
are gated exactly.  See README "Kernels".

Run:
  PYTHONPATH=src python -m benchmarks.codec_bench            # write json
  PYTHONPATH=src python -m benchmarks.codec_bench --check    # CI gate
"""
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

import repro.core.compressors as C
from repro.transport import codecs

SHAPE = (64, 4096)        # a boundary-sized (microbatch, features) tensor
K_FRAC = 0.10
ITERS = 30


def _timeit(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / ITERS


def _on_backend(backend, fn, *args):
    prev = C.KERNEL_BACKEND
    try:
        C.KERNEL_BACKEND = backend
        return fn(*args)
    finally:
        C.KERNEL_BACKEND = prev


def _gbps(nbytes, seconds):
    return round(nbytes / seconds / 1e9, 3)


def _codec_parity(name, x, pj, pp):
    """True iff the Pallas payload honors the codec's wire contract vs
    jnp: q4/none bit-exact bytes, TopK set-equal (dense roundtrip
    identical), q8 within its per-tile quantization error bound."""
    if name == "topk":
        if pj["idx"].shape != pp["idx"].shape or \
                pj["idx"].dtype != pp["idx"].dtype:
            return False
        for r in range(x.shape[0]):
            if (set(np.asarray(pj["idx"][r]).tolist())
                    != set(np.asarray(pp["idx"][r]).tolist())):
                return False
        dj = _on_backend("jnp", codecs.unpack_payload, pj, x.shape,
                         jnp.float32)
        dp = _on_backend("jnp", codecs.unpack_payload, pp, x.shape,
                         jnp.float32)
        return bool(np.array_equal(np.asarray(dj), np.asarray(dp)))
    if name == "q8" and set(pp) != set(pj):
        # per-tile Pallas format: same codes bytes count, finer scales —
        # check the reconstruction against the 8-bit error bound instead
        y = _on_backend("pallas", codecs.unpack_payload, pp, x.shape,
                        jnp.float32)
        step = float(jnp.max(x) - jnp.min(x)) / 255
        return bool(float(jnp.abs(y - x).max()) <= step + 1e-5)
    for k in pj:
        if not np.array_equal(np.asarray(pj[k]), np.asarray(pp[k])):
            if k in ("codes4", "raw", "codes"):
                return False
            a, b = np.asarray(pj[k], np.float32), np.asarray(pp[k],
                                                             np.float32)
            if not np.allclose(a, b, rtol=0,
                               atol=1.2e-7 * max(np.abs(a).max(), 1.0)):
                return False
    return True


def measure_codecs(shape=SHAPE, k_frac=K_FRAC):
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    dense_bytes = x.size * 4
    tpu = jax.default_backend() == "tpu"
    rows = []
    for name in codecs.registered_codecs():
        packs, payloads = {}, {}
        for backend in ("jnp", "pallas"):
            fn = jax.jit(lambda a, nm=name, be=backend: _on_backend(
                be, codecs.get_codec(nm).pack, a, k_frac))
            packs[backend] = _timeit(fn, x)
            payloads[backend] = _on_backend(
                backend, codecs.get_codec(name).pack, x, k_frac)
        unpacks = {}
        for backend in ("jnp", "pallas"):
            p = payloads[backend]
            fn = jax.jit(lambda pl, nm=name, be=backend: _on_backend(
                be, codecs.unpack_payload, pl, shape, jnp.float32))
            unpacks[backend] = _timeit(fn, p)
        parity = _codec_parity(name, x, payloads["jnp"],
                               payloads["pallas"])
        for op, times in (("pack", packs), ("unpack", unpacks)):
            ratio = round(times["jnp"] / times["pallas"], 3)
            if tpu:
                # the acceptance gate: compiled kernels must win on-target
                assert ratio >= 1.0, (name, op, times)
            rows.append({
                "name": f"{name}:{op}", "codec": name, "op": op,
                "shape": list(shape), "k_frac": k_frac,
                "dense_bytes": dense_bytes,
                "wire_bytes_jnp": codecs.wire_bytes(payloads["jnp"]),
                "wire_bytes_pallas": codecs.wire_bytes(payloads["pallas"]),
                "jnp_gbps": _gbps(dense_bytes, times["jnp"]),
                "pallas_gbps": _gbps(dense_bytes, times["pallas"]),
                "pallas_over_jnp": ratio,
                "parity": parity,
                "perf_gate": "enforced" if tpu else "tpu-only",
            })
    return rows


def measure_framing(shape=SHAPE):
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    payload = _on_backend("jnp", codecs.get_codec("q8").pack, x)
    nbytes = codecs.wire_bytes(payload)
    tpu = jax.default_backend() == "tpu"
    fuse_t, bufs = {}, {}
    for backend in ("jnp", "pallas"):
        fn = jax.jit(lambda p, be=backend: _on_backend(
            be, codecs.fuse_payload, p))
        fuse_t[backend] = _timeit(fn, payload)
        bufs[backend] = _on_backend(backend, codecs.fuse_payload, payload)
    identical = bool(np.array_equal(np.asarray(bufs["jnp"]),
                                    np.asarray(bufs["pallas"])))
    unfuse_t = {}
    for backend in ("jnp", "pallas"):
        fn = jax.jit(lambda b, be=backend: _on_backend(
            be, codecs.unfuse_payload, b, payload))
        unfuse_t[backend] = _timeit(fn, bufs[backend])
    rows = []
    for op, times in (("fuse", fuse_t), ("unfuse", unfuse_t)):
        ratio = round(times["jnp"] / times["pallas"], 3)
        if tpu:
            assert ratio >= 1.0, (op, times)
        rows.append({
            "name": f"framing:{op}", "op": op,
            "payload_leaves": len(jax.tree.leaves(payload)),
            "buffer_bytes": nbytes,
            "jnp_gbps": _gbps(nbytes, times["jnp"]),
            "pallas_gbps": _gbps(nbytes, times["pallas"]),
            "pallas_over_jnp": ratio,
            "byte_identical": identical,
            "perf_gate": "enforced" if tpu else "tpu-only",
        })
    return rows


def measure_dp_decode(dp=4, leaf_shapes=((128, 129), (2048,), (33,))):
    """Fused decode+sum kernel vs the unfused unpack->add reference loop,
    on hop buffers laid out as rank 0 of the ring holds them (no mesh
    needed)."""
    from repro.kernels.dp_reduce import (build_decode_plans, decode_fits,
                                         decode_sum_fused)
    from repro.transport.collectives import pack_grad_leaf, unpack_grad_leaf
    tpu = jax.default_backend() == "tpu"
    rows = []
    for codec_name in ("q8", "q4"):
        codec = codecs.get_codec(codec_name)
        per_src = []
        for s in range(dp):
            leaves = [jax.random.normal(jax.random.PRNGKey(7 * s + i), sh)
                      for i, sh in enumerate(leaf_shapes)]
            per_src.append([pack_grad_leaf(codec, a) for a in leaves])
        bufs = [_on_backend("jnp", codecs.fuse_payload, p) for p in per_src]
        hops = [bufs[-h % dp] for h in range(dp)]
        struct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), per_src[0])
        plans = build_decode_plans(struct, list(leaf_shapes))
        assert plans is not None and decode_fits(plans, dp), codec_name

        def reference(hs):
            acc = [None] * len(leaf_shapes)
            for s in range(dp):
                pls = codecs.unfuse_payload(hs[-s % dp], struct)
                for i, sh in enumerate(leaf_shapes):
                    m = unpack_grad_leaf(codec, pls[i], sh)
                    acc[i] = m if acc[i] is None else acc[i] + m
            return acc

        def ref_fn(hs):
            return _on_backend("jnp", reference, hs)

        def fused_fn(hs):
            return decode_sum_fused(hs, plans, jnp.int32(0))

        t_ref = _timeit(jax.jit(ref_fn), hops)
        t_fused = _timeit(jax.jit(fused_fn), hops)
        want = ref_fn(hops)
        got = fused_fn(hops)
        ok = all(
            np.allclose(np.asarray(g).reshape(-1), np.asarray(w).reshape(-1),
                        rtol=0,
                        atol=dp * 1.2e-7 * max(float(np.abs(np.asarray(w))
                                                     .max()), 1.0))
            for g, w in zip(got, want))
        dense_bytes = sum(int(np.prod(sh)) for sh in leaf_shapes) * 4 * dp
        ratio = round(t_ref / t_fused, 3)
        if tpu:
            assert ratio >= 1.0, (codec_name, t_ref, t_fused)
        rows.append({
            "name": f"dp_decode_sum:{codec_name}", "codec": codec_name,
            "dp": dp, "leaves": len(leaf_shapes),
            "hop_buffer_bytes": int(hops[0].shape[0]),
            "dense_bytes": dense_bytes,
            "jnp_gbps": _gbps(dense_bytes, t_ref),
            "pallas_gbps": _gbps(dense_bytes, t_fused),
            "pallas_over_jnp": ratio,
            "parity": bool(ok),
            "perf_gate": "enforced" if jax.default_backend() == "tpu"
                         else "tpu-only",
        })
    return rows


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="regression gate: recompute and compare against "
                         "the committed results/codec_bench.json (parity "
                         "booleans and wire bytes exact, throughputs "
                         "banded); exit 1 on drift")
    args = ap.parse_args(argv)
    codec_rows = measure_codecs()
    framing_rows = measure_framing()
    dp_rows = measure_dp_decode()
    for r in codec_rows + framing_rows + dp_rows:
        print(json.dumps(r))
    bad = [r["name"] for r in codec_rows + framing_rows + dp_rows
           if not r.get("parity", r.get("byte_identical", True))]
    assert not bad, f"kernel/jnp parity broken: {bad}"
    fresh = {"backend": jax.default_backend(), "codecs": codec_rows,
             "framing": framing_rows, "dp_decode_sum": dp_rows}
    if args.check:
        from benchmarks.common import run_check
        # parity booleans, wire bytes and payload structure gate exactly.
        # Interpret-mode throughputs on shared CPU runners swing several x
        # run-to-run (tiny kernels, cache effects), so the gbps/ratio
        # numbers are recorded for information only — the >= jnp perf gate
        # is the in-code assertion above, enforced when the backend is TPU.
        return run_check(
            fresh, "codec_bench",
            ignore_keys=frozenset(
                {"jnp_gbps", "pallas_gbps", "pallas_over_jnp"}))
    results = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(results, exist_ok=True)
    with open(os.path.join(results, "codec_bench.json"), "w") as f:
        json.dump(fresh, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
