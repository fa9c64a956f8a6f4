"""AdamW optimizer + LR schedules, pure JAX: the surrogate's full-batch trainer.

Moments are fp32 and shaped like the parameters.  Global-norm clipping and
decoupled weight decay included.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1


class OptState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


def lr_at(cfg: OptConfig, step):
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    prog = (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def init(params) -> OptState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return OptState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree.map(zeros, params),
        nu=jax.tree.map(zeros, params),
    )


def global_norm(tree):
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def apply(cfg: OptConfig, params, grads, state: OptState):
    """Returns (new_params, new_state, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    step = state.step + 1
    lr = lr_at(cfg, step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * g * g
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.mu)
    flat_v = jax.tree.leaves(state.nu)
    new = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(tdef, [n[0] for n in new])
    new_m = jax.tree.unflatten(tdef, [n[1] for n in new])
    new_v = jax.tree.unflatten(tdef, [n[2] for n in new])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, OptState(step, new_m, new_v), metrics
