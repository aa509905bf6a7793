"""Plain float32 reference of the dense decoder LM and its training steps.

Follows the configuration as run (bench/configs/*.json): pre-norm blocks
of LayerNorm, causal GQA attention with rotate-half RoPE, a GeLU (tanh)
MLP, no biases, a tied LM head; next-token cross entropy with the last
position masked; the cell's codec at each simulated stage cut, forward on
the activation and backward on its gradient (reference/codecs.py);
AdamW with global-norm clipping and a cosine learning rate.  Every matmul
runs at ``Precision.HIGHEST`` (true float32 on the TPU).  The weights are
the benchmark's own (bench/weights.py), upcast to float32.

``precision="fp8"`` is the control, the step below the program's bf16:
the weights kept in float8 e4m3 (rounded after every update, as the
program keeps them in bf16), every matmul operand rounded to e4m3 and
every gradient flowing back into one to e5m2, each under a per-tensor
scale.

Memory: each layer is rematerialised, attention runs in query chunks and
the loss in sequence chunks, and the gradients of earlier steps wait on
the host, so that a 3-layer StarCoder2-7B at 4096 tokens fits one chip.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from bench import weights
from bench.configs import sizes
from bench.reference import codecs

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 1024
LOSS_CHUNK = 256


def _round8(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jnp.clip(x / s, -top, top).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """The float8 training recipe (Micikevicius et al. 2022): a matmul
    operand rounded to e4m3 under a per-tensor scale, and the gradient
    that flows back through it rounded to e5m2 the same way."""
    return _round8(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_round8(g, jnp.float8_e5m2, 57344.0),))


@jax.jit
def _store8(p):
    """The control keeps its weights in e4m3 (per-tensor scale), as the
    program keeps its own in bf16."""
    return _round8(p, jnp.float8_e4m3fn, 448.0)


def _einsum(precision):
    def ein(spec, a, b, round_b=True):
        if precision == "fp8":
            a, b = _fp8(a), (_fp8(b) if round_b else b)
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return ein


def _layernorm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps) * p["scale"]
    return y + p["bias"] if "bias" in p else y


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    """x: (B, S, H, hd); rotate-half pairs (i, i + hd/2)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(p, x, sz, ein):
    norm = _layernorm if sz["norm"] == "layernorm" else _rmsnorm
    b, s, d = x.shape
    H, KV, hd = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
    h = norm(p["ln1"], x, sz["norm_eps"])
    q = ein("bsd,de->bse", h, p["attn"]["wq"]).reshape(b, s, H, hd)
    k = ein("bsd,de->bse", h, p["attn"]["wk"]).reshape(b, s, KV, hd)
    v = ein("bsd,de->bse", h, p["attn"]["wv"]).reshape(b, s, KV, hd)
    if sz["pos_embed"] == "rope":
        q, k = _rope(q, sz["rope_theta"]), _rope(k, sz["rope_theta"])
    g = H // KV
    q = q.reshape(b, s, KV, g, hd)
    outs = []
    for i in range(0, s, Q_CHUNK):
        qc = q[:, i:i + Q_CHUNK]
        n = qc.shape[1]
        logits = ein("bqkgd,btkd->bkgqt", qc, k) / math.sqrt(hd)
        causal = (np.arange(s)[None, :] <= np.arange(i, i + n)[:, None])
        logits = jnp.where(causal, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        outs.append(ein("bkgqt,btkd->bqkgd", probs, v))
    att = jnp.concatenate(outs, axis=1).reshape(b, s, H * hd)
    x = x + ein("bse,ed->bsd", att, p["attn"]["wo"])
    h = norm(p["ln2"], x, sz["norm_eps"])
    if sz["mlp"] == "swiglu":
        u = (jax.nn.silu(ein("bsd,df->bsf", h, p["mlp"]["wg"]))
             * ein("bsd,df->bsf", h, p["mlp"]["wi"]))
    else:
        u = _gelu(ein("bsd,df->bsf", h, p["mlp"]["wi"]))
    return x + ein("bsf,fd->bsd", u, p["mlp"]["wo"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _cut(fw, bw, x):
    return codecs.apply(fw, x)


def _cut_fwd(fw, bw, x):
    return codecs.apply(fw, x), None


def _cut_bwd(fw, bw, _, g):
    return (codecs.apply(bw, g),)


_cut.defvjp(_cut_fwd, _cut_bwd)


def segment_cuts(num_layers: int, num_stages: int):
    """Layer indices after which a stage cut sits: an even split of the
    layers into ``min(num_stages, num_layers)`` stages."""
    stages = min(num_stages, num_layers)
    per = num_layers / stages
    edges = [int(round(per * s)) for s in range(stages + 1)]
    return sorted({e for e in edges[1:-1] if 0 < e < num_layers})


def hidden(params, tokens, conf, policy, precision="f32"):
    """The final normed hidden states.  Each cut applies the cell's codec
    to the boundary tensor, and its backward codec to the gradient."""
    sz = sizes(conf)
    ein = _einsum(precision)
    cuts = segment_cuts(sz["num_layers"], policy["num_stages"])
    fw, bw = tuple(policy["fw"]), tuple(policy["bw"])
    x = params["embed"][tokens]
    layers = params["layers"]["b0"]
    block = jax.checkpoint(lambda p, x: _block(p, x, sz, ein))
    for li in range(sz["num_layers"]):
        x = block(jax.tree.map(lambda a: a[li], layers), x)
        if li + 1 in cuts:
            x = _cut(fw, bw, x)
    norm = _layernorm if sz["norm"] == "layernorm" else _rmsnorm
    return norm(params["final_norm"], x, sz["norm_eps"])


def loss(params, tokens, conf, policy, precision="f32"):
    """Mean next-token cross entropy (last position masked)."""
    ein = _einsum(precision)
    x = hidden(params, tokens, conf, policy, precision)
    b, s = tokens.shape
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)

    # the head's operand is rounded once for every chunk, not per chunk
    head = _fp8(params["embed"]) if precision == "fp8" else params["embed"]

    @jax.checkpoint
    def chunk_nll(xc, lc, mc):
        logits = ein("bsd,vd->bsv", xc, head, round_b=False)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return ((lse - pick) * mc).sum()

    total = 0.0
    for i in range(0, s, LOSS_CHUNK):
        total = total + chunk_nll(x[:, i:i + LOSS_CHUNK],
                                  labels[:, i:i + LOSS_CHUNK],
                                  mask[:, i:i + LOSS_CHUNK])
    return total / mask.sum()


def lr_at(opt: dict, step: int) -> float:
    lr, lr_min = opt["lr"], opt.get("lr_min", 0.0)
    if opt.get("schedule", "cosine") == "cosine":
        t = min(max(step / max(opt["t_max"], 1), 0.0), 1.0)
        lr = lr_min + 0.5 * (lr - lr_min) * (1 + math.cos(math.pi * t))
    return lr


def leaf_items(tree):
    """(name, array) per leaf, the stacked layer leaves split per layer."""
    out = []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("layers/"):
            out += [(f"{name}[{i}]", a[i]) for i in range(a.shape[0])]
        else:
            out.append((name, a))
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _grads(params, tokens, conf_frozen, policy_frozen, precision):
    conf, policy = weights.thaw(conf_frozen), weights.thaw(policy_frozen)
    val, g = jax.value_and_grad(loss)(params, tokens, conf, policy, precision)
    sq = sum(jnp.sum(x * x) for x in jax.tree.leaves(g))
    return val, g, jnp.sqrt(sq)


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a)))
                      for _, a in leaf_items(tree)])


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
def _adam_leaf(p, g, past, scale, hyper):
    """One AdamW update of one leaf at step t = len(past) + 1, the moments
    rebuilt from the clipped gradients of every step so far (``g`` is this
    step's gradient before its clipping ``scale``)."""
    b1, b2, eps, wd, lr = hyper
    gs = list(past) + [g * scale]
    t = len(gs)
    m = sum((1 - b1) * b1 ** (t - 1 - i) * x for i, x in enumerate(gs))
    v = sum((1 - b2) * b2 ** (t - 1 - i) * x * x for i, x in enumerate(gs))
    mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
    return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)


def train_readings(conf: dict, policy: dict, opt: dict, batches, seed: int,
                   precision: str = "f32") -> dict:
    """Follow the program's first ``len(batches)`` steps from the seed's
    weights.  Returns each step's loss, the per-leaf norms of the first
    (clipped) gradient, and the per-leaf norms of the weights' change
    after the last step, in :func:`leaf_items` order."""
    cf, pf = weights.freeze(conf), weights.freeze(policy)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make(conf, seed))
    flat, treedef = jax.tree.flatten(params)
    del params
    clip = opt.get("grad_clip", 0.0)
    hyper_base = (opt.get("beta1", 0.9), opt.get("beta2", 0.95),
                  opt.get("eps", 1e-8), opt.get("weight_decay", 0.0))
    past = [[] for _ in flat]        # host copies of earlier clipped grads
    losses, grad_norms = [], None
    for t, tokens in enumerate(batches, start=1):
        val, g, gnorm = _grads(jax.tree.unflatten(treedef, flat),
                               jnp.asarray(tokens), cf, pf, precision)
        scale = min(1.0, clip / (float(gnorm) + 1e-9)) if clip else 1.0
        losses.append(float(val))
        if grad_norms is None:
            grad_norms = np.asarray(_norms(g)) * scale
        g = jax.tree.leaves(g)
        hyper = hyper_base + (lr_at(opt, t),)
        last = t == len(batches)
        for i in range(len(flat)):
            gi = g[i]
            flat[i] = _adam_leaf(flat[i], gi, tuple(jnp.asarray(x)
                                                   for x in past[i]),
                                 jnp.float32(scale), hyper)
            if precision == "fp8":
                flat[i] = _store8(flat[i])
            if not last:
                past[i].append(np.asarray(gi) * np.float32(scale))
            g[i] = None
        del g, gi
    p0 = jax.tree.leaves(weights.make(conf, seed))
    change = [a - b.astype(jnp.float32) for a, b in zip(flat, p0)]
    del p0, flat
    change = jax.tree.unflatten(treedef, change)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": np.asarray(_norms(change)),
            "leaves": [n for n, _ in leaf_items(change)]}
