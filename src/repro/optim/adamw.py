"""AdamW with decoupled weight decay, global-norm clipping, and
param-sharded (ZeRO) optimizer state.

State is a pytree mirroring params: m and v in f32, sharded with the *same*
PartitionSpecs as their parameters so the optimizer never gathers anything —
the update is purely elementwise and runs fully sharded.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Any


class AdamWState(NamedTuple):
    step: jax.Array  # scalar int32
    m: Params  # f32, like params
    v: Params  # f32, like params


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr_peak: float = 3e-4
    lr_min: float = 3e-5
    warmup_steps: int = 200
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    # keep master params in f32? (params may themselves be bf16)
    mu_dtype: Any = jnp.float32


def cosine_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup then cosine decay to lr_min; pure jnp so it jits."""
    step = step.astype(jnp.float32)
    warm = cfg.lr_peak * step / max(cfg.warmup_steps, 1)
    decay_steps = max(cfg.total_steps - cfg.warmup_steps, 1)
    frac = jnp.clip((step - cfg.warmup_steps) / decay_steps, 0.0, 1.0)
    cos = cfg.lr_min + 0.5 * (cfg.lr_peak - cfg.lr_min) * (1 + jnp.cos(jnp.pi * frac))
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def init_state(params: Params, cfg: AdamWConfig) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, cfg.mu_dtype)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        m=jax.tree_util.tree_map(zeros, params),
        v=jax.tree_util.tree_map(zeros, params),
    )


def global_norm(tree: Params) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def clip_by_global_norm(grads: Params, max_norm: float) -> Tuple[Params, jax.Array]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype), grads), norm


def _decay_mask(path) -> bool:
    """Decay matmul kernels / embeddings; skip norms, biases, gains."""
    names = [str(e.key) for e in path if isinstance(e, jax.tree_util.DictKey)]
    leaf = names[-1] if names else ""
    no_decay = ("scale", "bias", "mu", "decay_base", "bonus_u", "b", "bq", "bk",
                "bv", "bo", "b_up", "b_down")
    return leaf not in no_decay


def apply_updates(
    params: Params,
    grads: Params,
    state: AdamWState,
    cfg: AdamWConfig,
    *,
    decay: Any = None,
) -> Tuple[Params, AdamWState, Dict[str, jax.Array]]:
    """One AdamW step. ``decay`` gives each leaf's weight-decay flag, as a
    pytree of bools like ``params``; by default ``_decay_mask`` reads it from
    the leaf's path."""
    if decay is None:
        decay = jax.tree_util.tree_map_with_path(lambda path, _: _decay_mask(path), params)
    grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    step = state.step + 1
    lr = cosine_schedule(cfg, step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    new_m = jax.tree_util.tree_map(
        lambda m, g: cfg.b1 * m + (1 - cfg.b1) * g, state.m, grads
    )
    new_v = jax.tree_util.tree_map(
        lambda v, g: cfg.b2 * v + (1 - cfg.b2) * jnp.square(g), state.v, grads
    )

    def upd(p, m, v, d):
        u = (m / b1c) / (jnp.sqrt(v / b2c) + cfg.eps)
        if cfg.weight_decay and d:
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

    new_params = jax.tree_util.tree_map(upd, params, new_m, new_v, decay)
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, AdamWState(step, new_m, new_v), metrics
