"""Seeded weights, made on the device in one jitted call.

The pytree has the layout the repo's dense decoder takes
(``repro.models.transformer``: ``embed``, ``layers.b0`` stacked over the
layers, ``final_norm``): matmul weights in ``dtype`` (bf16, as the
program trains and serves them), norm scales and biases in float32.  The
recipe is the benchmark's own, so that the reference can make the very
same weights from the seed without taking anything from the program:
normal with standard deviation 1/sqrt(fan_in) for the layers, 0.02 for
the embedding, unit scales and zero biases for the norms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.configs import sizes


def key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def shapes(conf: dict) -> dict:
    """Leaf shapes of the matmul weights, by path."""
    s = sizes(conf)
    L, d, hd, ff = s["num_layers"], s["d_model"], s["head_dim"], s["d_ff"]
    shp = {"embed": (s["vocab_size"], d),
           "layers/b0/attn/wq": (L, d, s["num_heads"] * hd),
           "layers/b0/attn/wk": (L, d, s["num_kv_heads"] * hd),
           "layers/b0/attn/wv": (L, d, s["num_kv_heads"] * hd),
           "layers/b0/attn/wo": (L, s["num_heads"] * hd, d),
           "layers/b0/mlp/wi": (L, d, ff),
           "layers/b0/mlp/wo": (L, ff, d)}
    if s["mlp"] == "swiglu":
        shp["layers/b0/mlp/wg"] = (L, d, ff)
    return shp


def _norm(n_layers, d, layernorm):
    p = {"scale": jnp.ones((d,) if n_layers is None else (n_layers, d),
                           jnp.float32)}
    if layernorm:
        p["bias"] = jnp.zeros_like(p["scale"])
    return p


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(frozen_conf, k, dtype):
    conf = thaw(frozen_conf)
    s = sizes(conf)
    shp = shapes(conf)
    keys = jax.random.split(k, len(shp))
    flat = {}
    for kk, (path, shape) in zip(keys, sorted(shp.items())):
        std = 0.02 if path == "embed" else shape[-2] ** -0.5
        flat[path] = (jax.random.normal(kk, shape, jnp.float32)
                      * std).astype(dtype)
    params = _nest(flat)
    ln = s["norm"] == "layernorm"
    params["layers"]["b0"]["ln1"] = _norm(s["num_layers"], s["d_model"], ln)
    params["layers"]["b0"]["ln2"] = _norm(s["num_layers"], s["d_model"], ln)
    params["final_norm"] = _norm(None, s["d_model"], ln)
    return params


def freeze(x):
    """A hashable form of a JSON value (a jit static argument)."""
    if isinstance(x, dict):
        return tuple(sorted((k, freeze(v)) for k, v in x.items()))
    if isinstance(x, list):
        return ("__list__",) + tuple(freeze(v) for v in x)
    return x


def thaw(x):
    if isinstance(x, tuple) and x and x[0] == "__list__":
        return [thaw(v) for v in x[1:]]
    if isinstance(x, tuple):
        return {k: thaw(v) for k, v in x}
    return x


def make(conf: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The weights for ``seed``, on the default device."""
    return _make(freeze(conf), key(seed), dtype)
