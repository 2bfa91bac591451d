"""Plain reference of ResNet-V2 (pre-activation bottlenecks) as a classifier:
batch-statistics BatchNorm, a 7x7/2 stem and 3x3/2 max pool above 32x32
inputs, global average pooling, one dense head, mean cross entropy.

float32 throughout, products at HIGHEST precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refs.common import HIGHEST, Keys, q8, trunc_normal

OUT_LEAF = "['head']['w']"  # the output layer, by its path in the weights


def _conv_w(key, kh, kw, cin, cout):
    return {"w": trunc_normal(key, (kh, kw, cin, cout), (2.0 / (kh * kw * cin)) ** 0.5,
                              jnp.float32)}


def _bn(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def _plan(cfg):
    """(cin, width, cout, stride) of every bottleneck, stage by stage."""
    w0, cin, out = cfg["base_width"], cfg["base_width"], []
    for stage, n in enumerate(cfg["stages"]):
        width = w0 * 2**stage
        for b in range(n):
            out.append((cin, width, width * 4, 2 if (b == 0 and stage > 0) else 1))
            cin = width * 4
    return out


def init(cfg: dict, key):
    ks = Keys(key)
    small = cfg["image_size"] <= 32
    k = 3 if small else 7
    params = {"stem": _conv_w(ks(), k, k, 3, cfg["base_width"]), "blocks": []}
    for cin, width, cout, _ in _plan(cfg):
        p = {"bn1": _bn(cin), "conv1": _conv_w(ks(), 1, 1, cin, width),
             "bn2": _bn(width), "conv2": _conv_w(ks(), 3, 3, width, width),
             "bn3": _bn(width), "conv3": _conv_w(ks(), 1, 1, width, cout)}
        if cin != cout:
            p["proj"] = _conv_w(ks(), 1, 1, cin, cout)
        params["blocks"].append(p)
    c = _plan(cfg)[-1][2]
    params["final_bn"] = _bn(c)
    params["head"] = {"w": trunc_normal(ks(), (c, cfg["num_classes"]), c**-0.5, jnp.float32)}
    return params


def conv(x, p, stride, lowp):
    w = p["w"]
    if lowp:
        x, w = q8(x), q8(w)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


def bn(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def logits(cfg: dict, lowp: bool, params, images):
    small = cfg["image_size"] <= 32
    x = conv(images, params["stem"], 1 if small else 2, lowp)
    if not small:
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for p, (_, _, _, stride) in zip(params["blocks"], _plan(cfg)):
        pre = jax.nn.relu(bn(x, p["bn1"]))
        if "proj" in p:
            short = conv(pre, p["proj"], stride, lowp)
        else:
            short = x[:, ::stride, ::stride, :]
        h = conv(pre, p["conv1"], 1, lowp)
        h = conv(jax.nn.relu(bn(h, p["bn2"])), p["conv2"], stride, lowp)
        h = conv(jax.nn.relu(bn(h, p["bn3"])), p["conv3"], 1, lowp)
        x = short + h
    x = jnp.mean(jax.nn.relu(bn(x, params["final_bn"])), axis=(1, 2))
    w = params["head"]["w"]
    if lowp:
        x, w = q8(x), q8(w)
    return jnp.einsum("nc,ck->nk", x, w, precision=HIGHEST)


def loss(cfg: dict, lowp: bool, params, batch):
    z = logits(cfg, lowp, params, batch["images"])
    lse = jax.nn.logsumexp(z, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(z, batch["labels"][:, None], axis=-1)[:, 0])
