"""Train/eval step builders: grad-accum, donation, and GSPMD sharding glue.

``build_train_step`` returns a pure function over a ``TrainState`` dict pytree
{"params", "opt"}; ``jit_train_step`` wraps it in ``jax.jit`` with in/out
shardings derived from the rule-based parameter PartitionSpecs and the
activation plan, donating the state so params/optimizer are updated in place.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSuite
from repro.models.model_api import Model
from repro.optim import adamw
from repro.sharding.plan import (
    ShardingPlan,
    make_plan,
    param_pspecs,
    validate_pspecs,
    zero_param_pspecs,
)

TrainState = Dict[str, Any]  # {"params": pytree, "opt": AdamWState}


def init_train_state(model: Model, key: jax.Array, opt_cfg: adamw.AdamWConfig):
    params = model.init(key)
    return {"params": params, "opt": adamw.init_state(params, opt_cfg)}


def build_train_step(
    model: Model,
    plan: ShardingPlan,
    opt_cfg: adamw.AdamWConfig,
    *,
    grad_accum: int = 1,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict]]:
    """Pure (state, batch) -> (state, metrics), with optional microbatching.

    grad_accum > 1 splits the global batch into ``grad_accum`` microbatches
    along dim 0 and accumulates grads in f32 under ``lax.scan`` — peak
    activation memory drops by ~grad_accum at the cost of re-running the
    (already rematerialized) forward.
    """

    def loss_fn(params, batch):
        return model.loss(params, batch, plan)

    # named scopes prefix the ops' metadata, so a profile can split the step
    # into forward/backward and optimizer
    def loss_and_grads(params, batch):
        with jax.named_scope("forward_backward"):
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def single(state, batch):
        (loss, metrics), grads = loss_and_grads(state["params"], batch)
        return loss, metrics, grads

    def accumulated(state, batch):
        def reshape(x):
            return x.reshape(grad_accum, x.shape[0] // grad_accum, *x.shape[1:])

        micro = jax.tree_util.tree_map(reshape, batch)
        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
        )

        def body(acc, mb):
            g_acc, loss_acc = acc
            (loss, metrics), g = loss_and_grads(state["params"], mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g
            )
            return (g_acc, loss_acc + loss), metrics

        (grads, loss_sum), metrics = jax.lax.scan(body, (g0, jnp.float32(0)), micro)
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return loss_sum / grad_accum, metrics, grads

    def train_step(state: TrainState, batch):
        loss, metrics, grads = (
            single(state, batch) if grad_accum == 1 else accumulated(state, batch)
        )
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw.apply_updates(
                state["params"], grads, state["opt"], opt_cfg
            )
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


# ---------------------------------------------------------------------------
# sharding glue
# ---------------------------------------------------------------------------


def state_shardings(model: Model, mesh: Mesh, variant: str = "baseline"):
    """NamedSharding pytree for the TrainState, from the rule-based pspecs."""
    params_shape = jax.eval_shape(model.init, jax.random.key(0))
    if variant == "zero":
        specs = zero_param_pspecs(params_shape, mesh)
    else:
        specs = validate_pspecs(params_shape, param_pspecs(params_shape), mesh)
    p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)
    scalar = NamedSharding(mesh, P())
    return {
        "params": p_sh,
        "opt": adamw.AdamWState(step=scalar, m=p_sh, v=p_sh),
    }


def batch_shardings(model: Model, mesh: Mesh, suite: ShapeSuite, plan: ShardingPlan):
    specs = model.input_specs(suite)
    batch_axes = plan.spec("tokens")[0] if len(plan.spec("tokens")) else None
    out = {}
    for k, v in specs.items():
        # batch dim over the data axes (when divisible — plan.spec('tokens')
        # already encodes the fallback), remaining dims unsharded.
        spec = P(batch_axes, *((None,) * (v.ndim - 1)))
        if k in ("patches", "frames"):
            spec = plan.spec("frames")
        out[k] = NamedSharding(mesh, spec)
    return out


def jit_train_step(
    model: Model,
    mesh: Mesh,
    suite: ShapeSuite,
    opt_cfg: adamw.AdamWConfig,
    *,
    grad_accum: int = 1,
    donate: bool = True,
    variant: str = "baseline",
):
    """jit'd train step + (state_shardings, batch_shardings) for callers."""
    plan = make_plan(model.cfg, mesh, suite, variant=variant)
    step_fn = build_train_step(model, plan, opt_cfg, grad_accum=grad_accum)
    st_sh = state_shardings(model, mesh, variant)
    b_sh = batch_shardings(model, mesh, suite, plan)
    jitted = jax.jit(
        step_fn,
        in_shardings=(st_sh, b_sh),
        out_shardings=(st_sh, None),
        donate_argnums=(0,) if donate else (),
    )
    return jitted, st_sh, b_sh, plan
