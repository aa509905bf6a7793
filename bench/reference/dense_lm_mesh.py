"""Plain float32 reference of the dense decoder LM trained on the pipeline
mesh: stage x tensor, with the cell's codec on every wire.

The model, loss, clipping and AdamW are bench/reference/dense_lm.py's
(its LayerNorm, rotate-half RoPE, GeLU, float8 control and AdamW update
are imported from there).  What the mesh adds is where the program's
wires cut the computation, and each cut applies the wire codec exactly
there (bench/reference/codecs.py arithmetic):

* the batch runs as ``microbatches`` microbatches; each block's residual
  is held as ``tensor`` sequence shards;
* a block gathers its normed shards before attention and before the MLP
  (each shard through the codec, in both directions of the gradient:
  the gather's gradient sums, per shard, each rank's own cotangent
  through the codec), and every rank computes its own heads (and its
  own slice of the MLP width) over the whole sequence;
* the partial outputs of the ``wo`` projections scatter back to the
  shards: shard j is the sum over the ranks of their slice j, each
  through the codec; the gradient gathers each shard's cotangent
  through the codec;
* between consecutive stages each microbatch's shards cross the stage
  wire through the codec forward, and their gradients backward.

Scale granularity follows the program's route: a q8 payload of at least
8 rows on the TPU is quantized per (rows, lane-block) tile by the Pallas
kernel, every other quantized payload per tensor (transport/codecs.py).
The stage wire carries (rows, S/tp * d) shards; the tensor wire
flattens each shard to one row, so it is always per tensor.

Memory: the layers live spread over the given devices (plain
``device_put``), are brought to the first device one at a time, and the
backward pass runs block by block from the stored block inputs, so that
1.96 B parameters in float32 with their gradients fit four 16 GiB chips.
Earlier steps' clipped gradients wait on the host, as in dense_lm.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from bench import weights
from bench.configs import sizes
from bench.reference import codecs, dense_lm
from bench.reference.dense_lm import _einsum, _fp8, _gelu, lr_at


def layout(policy: dict) -> dict:
    """The cell's ``policy`` block (``mesh``: "stage=2,tensor=2", ``wire``:
    "stage=q8,tensor=q8", ``microbatches``) as plain numbers and codec
    names."""
    def items(text):
        return dict(kv.split("=") for kv in text.split(","))
    mesh, wire = items(policy["mesh"]), items(policy["wire"])
    stages = int(mesh.get("stage", 1))
    return {"stages": stages, "tensor": int(mesh.get("tensor", 1)),
            "stage_wire": wire.get("stage", "none"),
            "tensor_wire": wire.get("tensor", "none"),
            "microbatches": int(policy.get("microbatches", stages))}


def _per_tensor(x, bits):
    levels = float((1 << bits) - 1)
    lo, hi = jnp.min(x), jnp.max(x)
    span = hi - lo
    scale = jnp.where(span > 0, span / levels, 1.0)
    return jnp.clip(jnp.round((x - lo) / scale), 0.0, levels) * scale + lo


def _pallas_route(rows: int, n: int) -> bool:
    """Where the program packs q8 with its per-tile kernel: on the TPU,
    a payload with an 8-row tiling (transport/codecs.wire_route)."""
    return (jax.default_backend() == "tpu"
            and codecs.tile(rows, n)[0] >= 8 and n % 128 == 0)


def wire(name: str, x, rows: int):
    """One payload through codec ``name`` ("none", "q8", "q4"): ``x``
    flattened to ``rows`` rows, as the program packs it."""
    if name == "none":
        return x
    bits = {"q8": 8, "q4": 4}[name]
    flat = x.reshape(rows, -1)
    if bits == 8 and _pallas_route(*flat.shape):
        return codecs.quant_dequant(flat, 8).reshape(x.shape)
    return _per_tensor(flat, bits).reshape(x.shape)


def _shards(x, tp):
    return jnp.split(x, tp, axis=1)


def _tensor_gather(name, tp):
    """(shards) -> one gathered copy per rank; the gradient of shard j is
    the sum over ranks of C(rank's cotangent, slice j)."""
    @jax.custom_vjp
    def gather(shards):
        full = jnp.concatenate([wire(name, s, 1) for s in shards], axis=1)
        return (full,) * tp

    def fwd(shards):
        return gather(shards), None

    def bwd(_, d_full):
        per = [_shards(d, tp) for d in d_full]
        return (tuple(sum(wire(name, per[r][j], 1) for r in range(tp))
                      for j in range(tp)),)
    gather.defvjp(fwd, bwd)
    return gather


def _tensor_scatter(name, tp):
    """(one partial per rank) -> shard j = sum over ranks of C(partial,
    slice j); the gradient of every partial is the gathered C(shards)."""
    @jax.custom_vjp
    def scatter(partials):
        per = [_shards(p, tp) for p in partials]
        return tuple(sum(wire(name, per[r][j], 1) for r in range(tp))
                     for j in range(tp))

    def fwd(partials):
        return scatter(partials), None

    def bwd(_, d_shards):
        full = jnp.concatenate([wire(name, d, 1) for d in d_shards], axis=1)
        return ((full,) * tp,)
    scatter.defvjp(fwd, bwd)
    return scatter


def _attention(pa, h, sz, ein, r, tp):
    """Rank ``r``'s heads over the whole sequence and its partial ``wo``
    output."""
    b, s, _ = h.shape
    hd = sz["head_dim"]
    H, KV = sz["num_heads"] // tp, sz["num_kv_heads"] // tp
    q = ein("bsd,de->bse", h, pa["wq"][:, r * H * hd:(r + 1) * H * hd])
    k = ein("bsd,de->bse", h, pa["wk"][:, r * KV * hd:(r + 1) * KV * hd])
    v = ein("bsd,de->bse", h, pa["wv"][:, r * KV * hd:(r + 1) * KV * hd])
    q, k = q.reshape(b, s, H, hd), k.reshape(b, s, KV, hd)
    v = v.reshape(b, s, KV, hd)
    if sz["pos_embed"] == "rope":
        q = dense_lm._rope(q, sz["rope_theta"])
        k = dense_lm._rope(k, sz["rope_theta"])
    q = q.reshape(b, s, KV, H // KV, hd)
    logits = ein("bqkgd,btkd->bkgqt", q, k) / math.sqrt(hd)
    causal = np.arange(s)[None, :] <= np.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    att = ein("bkgqt,btkd->bqkgd", probs, v).reshape(b, s, H * hd)
    return ein("bse,ed->bsd", att, pa["wo"][r * H * hd:(r + 1) * H * hd])


def _mlp(pm, h, ein, r, tp):
    f = pm["wi"].shape[1] // tp
    u = _gelu(ein("bsd,df->bsf", h, pm["wi"][:, r * f:(r + 1) * f]))
    return ein("bsf,fd->bsd", u, pm["wo"][r * f:(r + 1) * f])


def block(p, x, sz, lay, precision):
    """One layer on one microbatch ``x`` (b, S, d), its residual held as
    sequence shards, its matmuls split over the tensor ranks."""
    ein = _einsum(precision)
    tp, name = lay["tensor"], lay["tensor_wire"]
    norm = dense_lm._layernorm if sz["norm"] == "layernorm" \
        else dense_lm._rmsnorm
    gather, scatter = _tensor_gather(name, tp), _tensor_scatter(name, tp)
    xs = _shards(x, tp)
    fulls = gather(tuple(norm(p["ln1"], s, sz["norm_eps"]) for s in xs))
    outs = scatter(tuple(_attention(p["attn"], fulls[r], sz, ein, r, tp)
                         for r in range(tp)))
    xs = [s + o for s, o in zip(xs, outs)]
    fulls = gather(tuple(norm(p["ln2"], s, sz["norm_eps"]) for s in xs))
    outs = scatter(tuple(_mlp(p["mlp"], fulls[r], ein, r, tp)
                         for r in range(tp)))
    return jnp.concatenate([s + o for s, o in zip(xs, outs)], axis=1)


def stage_hop(x, lay):
    """A microbatch's shards across the stage wire (forward on the
    activation, backward on its gradient: the same codec)."""
    rows = x.shape[0]
    return jnp.concatenate([wire(lay["stage_wire"], s, rows)
                            for s in _shards(x, lay["tensor"])], axis=1)


def head_nll(top, x, labels, mask, sz, precision):
    """Summed next-token NLL of one microbatch from its last hidden
    states (final norm, tied head), over sequence chunks."""
    ein = _einsum(precision)
    norm = dense_lm._layernorm if sz["norm"] == "layernorm" \
        else dense_lm._rmsnorm
    x = norm(top["final_norm"], x, sz["norm_eps"])
    head = _fp8(top["embed"]) if precision == "fp8" else top["embed"]
    total = 0.0
    for i in range(0, x.shape[1], dense_lm.LOSS_CHUNK):
        sl = slice(i, i + dense_lm.LOSS_CHUNK)
        logits = ein("bsd,vd->bsv", x[:, sl], head, round_b=False)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, labels[:, sl, None], axis=-1)
        total = total + ((lse - pick[..., 0]) * mask[:, sl]).sum()
    return total


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block_fwd(p, x, szf, layf, precision):
    return block(p, x, weights.thaw(szf), weights.thaw(layf), precision)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _block_vjp(p, x, g, szf, layf, precision):
    _, pull = jax.vjp(lambda p, x: block(p, x, weights.thaw(szf),
                                         weights.thaw(layf), precision), p, x)
    return pull(g)


@functools.partial(jax.jit, static_argnums=(1,))
def _hop(x, layf):
    return stage_hop(x, weights.thaw(layf))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(top, x, labels, mask, szf, precision):
    val, pull = jax.vjp(lambda t, x: head_nll(t, x, labels, mask,
                                              weights.thaw(szf), precision),
                        top, x)
    return val, pull(jnp.float32(1.0))


@jax.jit
def _embed_vjp(embed, tokens, g):
    return jax.vjp(lambda e: e[tokens], embed)[1](g)[0]


@jax.jit
def _sq(tree):
    return sum(jnp.sum(a * a) for a in jax.tree.leaves(tree))


def _scale(tree, s):
    return jax.tree.map(lambda a: a * s, tree)


def _add(a, b):
    return b if a is None else jax.tree.map(jnp.add, a, b)


def leaf_names(conf: dict):
    """The program's per-leaf order (``dense_lm.leaf_items``): names, and
    for each the (layer or None, path) it reads."""
    shapes = jax.eval_shape(lambda: weights.make(conf, 0))
    out = []
    for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = tuple(str(getattr(k, "key", k)) for k in path)
        name = "/".join(keys)
        if keys[0] == "layers":
            out += [(f"{name}[{i}]", i, keys[2:]) for i in range(a.shape[0])]
        else:
            out.append((name, None, keys))
    return out


def _get(tree, keys):
    for k in keys:
        tree = tree[k]
    return tree


def _dev(a):
    return next(iter(a.devices()))


def _grads(top, layers, dev0, homes, tokens, sz, lay, precision):
    """Loss, the gradients of ``top`` (on ``dev0``, which computes) and
    of each layer (on its home device) over the microbatches."""
    L, S, mb = sz["num_layers"], lay["stages"], lay["microbatches"]
    cuts = {L * k // S for k in range(1, S)}
    szf, layf = weights.freeze(sz), weights.freeze(lay)
    tokens = jax.device_put(tokens, dev0)
    b, s = tokens.shape
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)
    denom = float(mask.sum())
    total, g_top, g_layers = 0.0, None, [None] * L
    rows = b // mb
    for m in range(mb):
        tok = tokens[m * rows:(m + 1) * rows]
        x = top["embed"][tok]
        xs = []
        for li in range(L):
            if li in cuts:
                x = _hop(x, layf)
            xs.append(jax.device_put(x, homes[li]))
            x = _block_fwd(jax.device_put(layers[li], dev0), x, szf, layf,
                           precision)
        nll, (gt, gx) = _head(top, x, labels[m * rows:(m + 1) * rows],
                              mask[m * rows:(m + 1) * rows], szf, precision)
        total += float(nll)
        g_top = _add(g_top, gt)
        for li in reversed(range(L)):
            gp, gx = _block_vjp(jax.device_put(layers[li], dev0),
                                jax.device_put(xs[li], dev0), gx, szf, layf,
                                precision)
            g_layers[li] = _add(g_layers[li], jax.device_put(gp, homes[li]))
            xs[li] = None
            if li in cuts:
                gx = _hop(gx, layf)
        g_top["embed"] = g_top["embed"] + _embed_vjp(top["embed"], tok, gx)
    inv = 1.0 / denom
    return (total * inv, _scale(g_top, inv),
            [_scale(g, inv) for g in g_layers])


def train_readings(conf: dict, policy: dict, opt: dict, batches, seed: int,
                   precision: str = "f32", devices=None) -> dict:
    """Follow the program's first ``len(batches)`` steps from the seed's
    weights; the readings of ``dense_lm.train_readings`` (each step's
    loss, the per-leaf norms of the first clipped gradient and of the
    weights' change, in ``leaf_items`` order)."""
    sz, lay = sizes(conf), layout(policy)
    if sz["num_heads"] % lay["tensor"] or sz["num_kv_heads"] % lay["tensor"]:
        raise ValueError("the heads do not split over the tensor ranks")
    devices = list(devices or jax.devices())
    L = sz["num_layers"]
    # the first device computes; the layers live on the others
    dev0, store = devices[0], devices[1:] or devices
    homes = [store[li * len(store) // L] for li in range(L)]
    p0 = weights.make(conf, seed)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    top = jax.device_put(f32({"embed": p0["embed"],
                              "final_norm": p0["final_norm"]}), dev0)
    layers = [jax.device_put(f32(jax.tree.map(lambda a: a[li],
                                              p0["layers"]["b0"])), homes[li])
              for li in range(L)]
    del p0
    clip = opt.get("grad_clip", 0.0)
    hyper_base = (opt.get("beta1", 0.9), opt.get("beta2", 0.95),
                  opt.get("eps", 1e-8), opt.get("weight_decay", 0.0))
    past_top, past_layers = [], [[] for _ in range(L)]
    losses, grad_norms = [], None
    names = leaf_names(conf)
    for t, tokens in enumerate(batches, start=1):
        val, g_top, g_layers = _grads(top, layers, dev0, homes,
                                      jnp.asarray(tokens), sz, lay, precision)
        sq = float(_sq(g_top)) + sum(float(_sq(g)) for g in g_layers)
        scale = min(1.0, clip / (math.sqrt(sq) + 1e-9)) if clip else 1.0
        losses.append(val)
        if grad_norms is None:
            grad_norms = _norms(names, g_top, g_layers) * scale
        hyper = hyper_base + (lr_at(opt, t),)
        last = t == len(batches)
        top, past_top = _update(top, g_top, past_top, scale, hyper, last,
                                precision)
        for li in range(L):
            layers[li], past_layers[li] = _update(
                layers[li], g_layers[li], past_layers[li], scale, hyper,
                last, precision)
        del g_top, g_layers
    p0 = weights.make(conf, seed)
    moved = lambda a, b: a - jax.device_put(b, _dev(a)).astype(jnp.float32)
    change_top = jax.tree.map(moved, top, {"embed": p0["embed"],
                                           "final_norm": p0["final_norm"]})
    change_layers = [jax.tree.map(lambda a, b: moved(a, b[li]), layers[li],
                                  p0["layers"]["b0"]) for li in range(L)]
    del p0
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": _norms(names, change_top, change_layers),
            "leaves": [n for n, _, _ in names]}


def _norms(names, top, layers) -> np.ndarray:
    return np.asarray([
        float(jnp.sqrt(jnp.sum(jnp.square(
            _get(top if li is None else layers[li], keys)))))
        for _, li, keys in names])


def _update(tree, g, past, scale, hyper, last, precision):
    """AdamW (dense_lm's update) on every leaf of one block of weights,
    on the device that holds it; this step's clipped gradient joins the
    host-side history unless it is the last step."""
    leaves, treedef = jax.tree.flatten(tree)
    gl = jax.tree.leaves(g)
    hist = past if past else [[] for _ in leaves]
    out = []
    for i, (p, gi) in enumerate(zip(leaves, gl)):
        dev = _dev(p)
        prev = tuple(jax.device_put(x, dev) for x in hist[i])
        p = dense_lm._adam_leaf(p, gi, prev, jnp.float32(scale), hyper)
        if precision == "fp8":
            p = dense_lm._store8(p)
        if not last:
            hist[i].append(np.asarray(gi) * np.float32(scale))
        out.append(p)
    return jax.tree.unflatten(treedef, out), hist
