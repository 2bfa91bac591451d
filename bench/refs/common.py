"""What the plain references share: float32 products at HIGHEST precision,
the per-tensor fp8 rounding of the control, the initialisers, AdamW, and the
three checked steps with their readings.

Nothing here imports the program. The weights are made again from the
seed's key with the published initialisers, in the same order of keys.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def q8(x: jax.Array) -> jax.Array:
    """x rounded to float8 e4m3 under one scale per tensor (the control).
    Gradients pass straight through, unrounded."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / F8_MAX)
    s = jnp.where(s > 0, s, 1.0)
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def dot(eq: str, a, b, lowp: bool):
    if lowp:
        a, b = q8(a), q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def trunc_normal(key, shape, std, dtype):
    """Normal truncated at two standard deviations, scaled to ``std``."""
    x = jax.random.truncated_normal(key, -2.0, 2.0, tuple(shape), jnp.float32)
    return (x * std).astype(dtype)


class Keys:
    """Successive subkeys: each call splits the running key in two and
    hands out the second half."""

    def __init__(self, key):
        self.key = key

    def __call__(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``lr_min`` over ``total_steps``."""
    peak, low = opt["lr_peak"], opt.get("lr_min", 3e-5)
    warm, total = opt.get("warmup_steps", 0), opt["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return low + 0.5 * (peak - low) * (1 + math.cos(math.pi * frac))


# AdamW as published (decoupled decay), no decay on norm gains and biases
NO_DECAY = ("scale", "bias")


def _decayed(path) -> bool:
    return jax.tree_util.keystr(path[-1:]).strip("[]'\"") not in NO_DECAY


@functools.partial(jax.jit, donate_argnums=(0, 1), static_argnames=("hp", "decay"))
def _adamw_leaf(p, g, m, v, lr, b1c, b2c, scale, *, hp, decay):
    b1, b2, eps, wd = hp
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / b1c) / (jnp.sqrt(v / b2c) + eps)
    if decay:
        u = u + wd * p.astype(jnp.float32)
    return (p.astype(jnp.float32) - lr * u).astype(p.dtype), m, v


def _value_and_grads(params, batch, *, loss):
    """Loss and float32 gradients: taken at float32 copies of the weights,
    so no gradient is rounded to the weights' storage type."""
    up = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    return jax.value_and_grad(lambda p: loss(p, batch))(up)


_loss_and_grads = jax.jit(_value_and_grads, static_argnames=("loss",))


def spread(devices):
    """The placement of the reference over several devices: each array is
    laid along its largest dimension that their number divides (the first
    of equal ones), and copied whole to each where none does. The
    benchmark's own rule: it takes nothing from the program's sharding."""
    mesh = Mesh(np.asarray(devices), ("spread",))
    n = len(devices)

    def place(shape) -> NamedSharding:
        dims = sorted((d for d in range(len(shape)) if shape[d] % n == 0),
                      key=lambda d: -shape[d])
        spec = [None] * len(shape)
        if dims:
            spec[dims[0]] = "spread"
        return NamedSharding(mesh, P(*spec))

    return place


def laid(place, tree):
    """The sharding of each array of ``tree`` (arrays or shapes) under ``place``."""
    return jax.tree_util.tree_map(lambda x: place(x.shape), tree)


def loss_and_grads(place, params):
    """``_loss_and_grads``; under a placement, with the gradients laid out
    as ``params``."""
    if place is None:
        return _loss_and_grads
    return jax.jit(_value_and_grads, static_argnames=("loss",),
                   out_shardings=(None, laid(place, params)))


@jax.jit
def _change(p, p0):
    return _norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))


def three_steps(init, loss, key, batches, opt: dict, out_leaf: str, place=None) -> dict:
    """Three AdamW steps of ``loss`` from ``init(key)`` over ``batches``:
    each loss, each global gradient norm before clipping, the norms of the
    first gradient after clipping by the global norm, the whole first
    gradient of the output layer ``out_leaf`` before clipping, and each
    leaf's change after the three steps.

    On one device the moments stay in host memory and each leaf is updated
    on its own, so that the device holds no more than the weights, one
    float32 copy of them and their gradients at once. Under ``place``
    (``spread``) the weights, their gradients and the moments are laid over
    the devices, the moments kept there; the arithmetic is the same.
    """
    shapes = jax.eval_shape(init, key)
    if place is None:
        make = jax.jit(init)
        zeros = lambda x: np.zeros(x.shape, np.float32)  # noqa: E731
        fetch = np.asarray
    else:
        make = jax.jit(init, out_shardings=laid(place, shapes))
        zeros = lambda x: jnp.zeros(x.shape, jnp.float32, device=place(x.shape))  # noqa: E731
        fetch = lambda x: x  # noqa: E731
    grads_of = loss_and_grads(place, shapes)
    params = make(key)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    names = [jax.tree_util.keystr(p) for p, _ in leaves]
    decay = [_decayed(p) for p, _ in leaves]
    params = [x for _, x in leaves]
    m = [zeros(x) for x in params]
    v = [zeros(x) for x in params]
    b1, b2 = opt.get("b1", 0.9), opt.get("b2", 0.95)
    hp = (b1, b2, opt.get("eps", 1e-8), opt.get("weight_decay", 0.1))
    losses, gnorms, grad, out = [], [], None, None
    for step, batch in enumerate(batches, start=1):
        value, grads = grads_of(tree.unflatten(params), batch, loss=loss)
        grads = jax.tree_util.tree_leaves(grads)
        gn = np.asarray(_norms(grads), np.float64)
        scale = min(1.0, opt.get("clip_norm", 1.0) / max(float(np.sqrt(np.sum(gn**2))), 1e-9))
        losses.append(float(value))
        gnorms.append(float(np.sqrt(np.sum(gn**2))))
        if grad is None:
            grad = gn * scale
            out = np.asarray(grads[names.index(out_leaf)], np.float32)
        for i, g in enumerate(grads):
            params[i], mi, vi = _adamw_leaf(
                params[i], g, m[i], v[i], lr_at(opt, step), 1 - b1**step,
                1 - b2**step, scale, hp=hp, decay=decay[i])
            m[i], v[i] = fetch(mi), fetch(vi)
        del grads
    del m, v
    change = np.asarray(_change(tree.unflatten(params), make(key)))
    return {"losses": losses, "gnorms": gnorms, "grad": dict(zip(names, grad.tolist())),
            "out_grad": out, "change": dict(zip(names, change.tolist()))}
