"""Train/eval step builders: grad-accum, donation, and GSPMD sharding glue.

``build_train_step`` returns a pure function over a ``TrainState`` dict pytree
{"params", "opt"}; ``jit_train_step`` wraps it in ``jax.jit`` with in/out
shardings derived from the rule-based parameter PartitionSpecs and the
activation plan, donating the state so params/optimizer are updated in place.

A model with many small parameter leaves gets a ``StackedState`` from
``init_train_state`` instead: leaves of one shape, dtype, decay flag and
sharding rule stacked into one array on a new leading axis, for the
parameters and both moments. The compiled step then takes and returns a few
dozen arrays rather than hundreds, which is most of its launch cost when the
step is short. The step unstacks inside the program, so the model sees its
own tree and the gradients come out stacked; ``state["params"]`` and
``state["opt"]`` read back the per-leaf trees, ``state_shardings`` places
each stacked array as its leaves, and a checkpoint holds the per-leaf tree.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections.abc import Mapping
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

TrainState = Dict[str, Any]  # {"params": pytree, "opt": AdamWState}; or a StackedState

# Only leaves up to this size are stacked. Each array the compiled step takes
# and returns costs its launch a few microseconds, whatever its size; but a
# stacked leaf is copied whenever the per-leaf view is read (``StackedState``)
# or the state is built, beside the state itself. Small leaves are most of
# the count and little of the bytes: in ResNet50-V2, 104 of 152 leaves and
# 0.55 of 98 MiB.
STACK_MAX_LEAF_BYTES = 64 * 2**10


def _split(stacked):
    """Each stacked array's slices along its leading axis. The transpose of
    ``lax.split`` is a concatenate, so gradients come out stacked."""
    return [[part.reshape(part.shape[1:]) for part in jax.lax.split(x, (1,) * x.shape[0])]
            for x in stacked]


_split_jit = jax.jit(_split)


class StackLayout:
    """Which parameter leaves each stored array holds.

    ``members[g]`` lists the leaf indices (in ``treedef`` order) of stored
    array g: one leaf is stored as it is, two or more are stacked on a new
    leading axis in that order. ``decay[g]`` is AdamW's decay flag of its
    leaves. Hashed once: jit compares it on every call.
    """

    __slots__ = ("treedef", "members", "decay", "_hash")

    def __init__(self, treedef, members, decay):
        self.treedef, self.members, self.decay = treedef, members, decay
        self._hash = hash((treedef, members, decay))

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return self is other or (
            isinstance(other, StackLayout) and self._hash == other._hash
            and (self.treedef, self.members, self.decay)
            == (other.treedef, other.members, other.decay))

    @classmethod
    def of(cls, params) -> Optional["StackLayout"]:
        """Group leaves of at most ``STACK_MAX_LEAF_BYTES`` by (shape, dtype,
        decay flag, ``param_pspecs`` rule), so that every sharding variant
        gives a group's leaves one sharding; None when that would not remove
        more than half the arrays."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        rules = jax.tree_util.tree_leaves(param_pspecs(params),
                                          is_leaf=lambda s: isinstance(s, P))
        groups: Dict[Any, list] = {}
        for i, ((path, x), rule) in enumerate(zip(flat, rules)):
            nbytes = math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
            key = ((x.shape, jnp.dtype(x.dtype), adamw._decay_mask(path), rule)
                   if nbytes <= STACK_MAX_LEAF_BYTES else i)
            groups.setdefault(key, []).append(i)
        if 2 * len(groups) >= len(flat):
            return None
        members = tuple(tuple(g) for g in groups.values())
        decay = tuple(adamw._decay_mask(flat[g[0]][0]) for g in members)
        return cls(treedef, members, decay)

    def stack(self, tree) -> Tuple[jax.Array, ...]:
        leaves = jax.tree_util.tree_leaves(tree)
        return tuple(jnp.stack([leaves[i] for i in g]) if len(g) > 1 else leaves[g[0]]
                     for g in self.members)

    def stack_shardings(self, shardings) -> Tuple[NamedSharding, ...]:
        """The stored arrays' shardings from the leaves' (``of`` gives a
        group one): a stacked array holds its leaves' on its trailing axes."""
        leaves = jax.tree_util.tree_leaves(shardings)
        return tuple(leaves[g[0]] if len(g) == 1
                     else NamedSharding(leaves[g[0]].mesh, P(None, *leaves[g[0]].spec))
                     for g in self.members)

    def unstack(self, stored, split=_split):
        """The model's tree from the stored arrays: a leaf stored alone is
        passed on as it is, the stacked arrays go through ``split``."""
        parts = iter(split([x for g, x in zip(self.members, stored) if len(g) > 1]))
        leaves = [None] * self.treedef.num_leaves
        for g, x in zip(self.members, stored):
            for i, leaf in zip(g, [x] if len(g) == 1 else next(parts)):
                leaves[i] = leaf
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


class StackedState(Mapping):
    """A train state stored as ``layout``'s stacked arrays.

    Its pytree leaves are the stored arrays (parameters, m, v, then the step
    counter). ``state["params"]`` and ``state["opt"]`` read back the model's
    tree and an ``AdamWState`` of such trees, under the paths of
    ``model.init``: a leaf stored alone is that array, a stacked one an
    exact slice (a copy, all of them in one compiled call). ``unstacked``
    and ``restack`` convert from and to the per-leaf state, which is what
    ``CheckpointStore`` writes and reads.
    """

    __slots__ = ("layout", "params", "m", "v", "step")

    def __init__(self, layout: StackLayout, params, m, v, step):
        self.layout, self.params, self.m, self.v, self.step = layout, params, m, v, step

    @classmethod
    def stack(cls, layout: StackLayout, state: TrainState) -> "StackedState":
        opt = state["opt"]
        return cls(layout, layout.stack(state["params"]), layout.stack(opt.m),
                   layout.stack(opt.v), opt.step)

    def unstacked(self) -> TrainState:
        """The per-leaf state: ``{"params": ..., "opt": AdamWState}``."""
        return {"params": self["params"], "opt": self["opt"]}

    def restack(self, state: TrainState) -> "StackedState":
        """A per-leaf state of this one's model, stored in this layout."""
        return StackedState.stack(self.layout, state)

    def _view(self, stored):
        abstract = any(isinstance(x, jax.ShapeDtypeStruct) for x in stored)
        return self.layout.unstack(
            stored, functools.partial(jax.eval_shape, _split) if abstract else _split_jit)

    def __getitem__(self, key):
        if key == "params":
            return self._view(self.params)
        if key == "opt":
            return adamw.AdamWState(self.step, self._view(self.m), self._view(self.v))
        raise KeyError(key)

    def __iter__(self):
        return iter(("params", "opt"))

    def __len__(self):
        return 2


_STACKED_KEYS = tuple(jax.tree_util.GetAttrKey(k) for k in ("params", "m", "v", "step"))

jax.tree_util.register_pytree_with_keys(
    StackedState,
    lambda s: (tuple(zip(_STACKED_KEYS, (s.params, s.m, s.v, s.step))), s.layout),
    lambda layout, children: StackedState(layout, *children),
    flatten_func=lambda s: ((s.params, s.m, s.v, s.step), s.layout),
)


def init_train_state(model: Model, key: jax.Array, opt_cfg: adamw.AdamWConfig):
    """Parameters and AdamW state from ``key``: a ``StackedState`` when
    ``StackLayout.of`` finds stacking worth it, else the per-leaf dict."""
    params = model.init(key)
    state = {"params": params, "opt": adamw.init_state(params, opt_cfg)}
    layout = StackLayout.of(params)
    return state if layout is None else StackedState.stack(layout, state)


def state_arrays(state) -> Dict[str, int]:
    """Arrays the compiled step takes as its state: per leaf (``before``)
    and as stored (``after``); equal unless the state is stacked."""
    after = len(jax.tree_util.tree_leaves(state))
    if isinstance(state, StackedState):
        return {"before": 3 * state.layout.treedef.num_leaves + 1, "after": after}
    return {"before": after, "after": after}


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

    # ``tree`` maps the stored parameters to the model's tree: the identity,
    # or a StackLayout's unstack
    def loss_fn(params, batch, tree):
        return model.loss(tree(params), batch, plan)

    # named scopes prefix the ops' metadata, so a profile can split the step
    # into forward/backward and optimizer
    def loss_and_grads(params, batch, tree):
        with jax.named_scope("forward_backward"):
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch, tree)

    def single(params, batch, tree):
        (loss, metrics), grads = loss_and_grads(params, batch, tree)
        return loss, metrics, grads

    def accumulated(params, batch, tree):
        def reshape(x):
            return x.reshape(grad_accum, x.shape[0] // grad_accum, *x.shape[1:])

        micro = jax.tree_util.tree_map(reshape, batch)
        g0 = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, mb):
            g_acc, loss_acc = acc
            (loss, metrics), g = loss_and_grads(params, mb, tree)
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g
            )
            return (g_acc, loss_acc + loss), metrics

        (grads, loss_sum), metrics = jax.lax.scan(body, (g0, jnp.float32(0)), micro)
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return loss_sum / grad_accum, metrics, grads

    def train_step(state: TrainState, batch):
        stacked = isinstance(state, StackedState)
        if stacked:
            layout = state.layout
            params, opt = state.params, adamw.AdamWState(state.step, state.m, state.v)
            tree, decay = layout.unstack, layout.decay
        else:
            params, opt = state["params"], state["opt"]
            tree, decay = (lambda p: p), None
        loss, metrics, grads = (
            single(params, batch, tree) if grad_accum == 1
            else accumulated(params, batch, tree)
        )
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw.apply_updates(
                params, grads, opt, opt_cfg, decay=decay
            )
        metrics = dict(metrics, loss=loss, **opt_metrics)
        if stacked:
            return StackedState(layout, new_params, new_opt.m, new_opt.v, new_opt.step), metrics
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


# ---------------------------------------------------------------------------
# sharding glue
# ---------------------------------------------------------------------------


def state_shardings(model: Model, mesh: Mesh, variant: str = "baseline"):
    """NamedSharding pytree for the TrainState, from the rule-based pspecs:
    per leaf, or per stored array of ``init_train_state``'s layout."""
    params_shape = jax.eval_shape(model.init, jax.random.key(0))
    if variant == "zero":
        specs = zero_param_pspecs(params_shape, mesh)
    else:
        specs = validate_pspecs(params_shape, param_pspecs(params_shape), mesh)
    p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)
    scalar = NamedSharding(mesh, P())
    layout = StackLayout.of(params_shape)
    if layout is not None:
        stored = layout.stack_shardings(p_sh)
        return StackedState(layout, stored, stored, stored, scalar)
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
