"""Pure-JAX optimizers (no optax offline): SGD+momentum+WD, AdamW,
cosine-annealing schedule.  Optimizer state is a pytree mirroring params;
moment dtype is configurable (bf16 moments keep the 400B MoE config inside
v5e HBM — see DESIGN.md §5)."""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs.trace import scope


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    kind: str = "sgd"                 # sgd | adamw
    lr: float = 0.01
    momentum: float = 0.9             # sgd
    beta1: float = 0.9                # adamw
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 5e-4
    grad_clip: float = 0.0            # 0 = off
    moment_dtype: Any = jnp.float32   # bf16 for the biggest configs
    # cosine schedule (paper: cosine annealing, T_max=200, lr0=0.01)
    schedule: str = "cosine"          # cosine | constant
    t_max: int = 200
    lr_min: float = 0.0
    warmup_steps: int = 0


def schedule_lr(cfg: OptimizerConfig, step: jnp.ndarray) -> jnp.ndarray:
    step = step.astype(jnp.float32)
    lr = jnp.float32(cfg.lr)
    if cfg.schedule == "cosine":
        t = jnp.clip(step / max(cfg.t_max, 1), 0.0, 1.0)
        lr = cfg.lr_min + 0.5 * (cfg.lr - cfg.lr_min) * (1 + jnp.cos(math.pi * t))
    if cfg.warmup_steps:
        lr = lr * jnp.clip(step / cfg.warmup_steps, 0.0, 1.0)
    return lr


def init_opt_state(cfg: OptimizerConfig, params):
    zeros = lambda p: jnp.zeros(p.shape, cfg.moment_dtype)
    state = {"step": jnp.zeros((), jnp.int32)}
    if cfg.kind == "sgd":
        state["mu"] = jax.tree.map(zeros, params)
    elif cfg.kind == "adamw":
        state["mu"] = jax.tree.map(zeros, params)
        state["nu"] = jax.tree.map(zeros, params)
    else:
        raise ValueError(cfg.kind)
    return state


def _global_norm(tree) -> jnp.ndarray:
    sq = jax.tree.map(lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), tree)
    return jnp.sqrt(jax.tree.reduce(lambda a, b: a + b, sq))


@scope("optimizer")
def apply_updates(cfg: OptimizerConfig, params, grads, state):
    """Returns (new_params, new_state)."""
    step = state["step"] + 1
    lr = schedule_lr(cfg, step)

    if cfg.grad_clip:
        gn = _global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / (gn + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)

    if cfg.kind == "sgd":
        def upd(p, g, m):
            gf = g.astype(jnp.float32)
            if cfg.weight_decay:
                gf = gf + cfg.weight_decay * p.astype(jnp.float32)
            m_new = cfg.momentum * m.astype(jnp.float32) + gf
            p_new = p.astype(jnp.float32) - lr * m_new
            return p_new.astype(p.dtype), m_new.astype(cfg.moment_dtype)
        out = jax.tree.map(upd, params, grads, state["mu"])
        new_params = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"step": step, "mu": new_mu}

    # adamw
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        v_new = b2 * v.astype(jnp.float32) + (1 - b2) * gf * gf
        mh = m_new / bc1
        vh = v_new / bc2
        p_new = (p.astype(jnp.float32)
                 - lr * (mh / (jnp.sqrt(vh) + cfg.eps)
                         + cfg.weight_decay * p.astype(jnp.float32)))
        return (p_new.astype(p.dtype), m_new.astype(cfg.moment_dtype),
                v_new.astype(cfg.moment_dtype))

    out = jax.tree.map(upd, params, grads, state["mu"], state["nu"])
    is_t = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is_t),
            {"step": step,
             "mu": jax.tree.map(lambda o: o[1], out, is_leaf=is_t),
             "nu": jax.tree.map(lambda o: o[2], out, is_leaf=is_t)})
