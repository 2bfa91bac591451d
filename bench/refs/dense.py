"""Plain reference of a dense GQA decoder LM (the granite-3.0 block as run):
pre-norm RMSNorm, rotary positions on split halves, grouped-query causal
softmax attention, SwiGLU MLP, tied embedding head, mean next-token cross
entropy plus a z-loss on the log-partition.

Where the configuration's ``assumed`` records that the program runs another
value than the published one (granite's RMSNorm epsilon and its unmodelled
multipliers), the reference follows the value run: the epsilon from
``assumed``, and no embedding, residual or logit multiplier, scores scaled
by 1/sqrt(head_dim).

Everything is computed in float32 at HIGHEST precision from weights stored
as the configuration states them (bfloat16 matrices, float32 gains).
Attention is computed exactly, one block of query rows at a time, and each
layer is recomputed in the backward pass, so that the reference fits one
chip at the benchmark's sizes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from refs.common import Keys, dot, trunc_normal

Q_BLOCK = 1024
LOSS_CHUNKS = 8
OUT_LEAF = "['embed']['table']"  # the output layer (tied), by its path in the weights


def padded_vocab(cfg: dict) -> int:
    """Rows of the embedding table: the vocabulary rounded up to 16."""
    return -(-cfg["vocab_size"] // 16) * 16


def as_run(cfg: dict, key: str):
    """The value the program runs for ``key``: the file's, unless ``assumed``
    records another."""
    a = cfg.get("assumed", {}).get(key)
    return a["run"] if isinstance(a, dict) else cfg[key]


def _dims(cfg):
    d, h, kvh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, h, kvh, cfg.get("head_dim") or d // h, cfg["intermediate_size"]


def init(cfg: dict, key):
    """Weights from the key, split in the order the published init uses."""
    d, h, kvh, hd, f = _dims(cfg)
    n = cfg["num_hidden_layers"]
    out_scale = 1.0 / math.sqrt(2 * n)
    bf16 = jnp.bfloat16

    def mat(k, shape, scale=1.0):  # fan-in scaled
        return trunc_normal(k, shape, scale / math.sqrt(shape[0]), bf16)

    def block(k):
        ks = Keys(k)
        ka, km = ks(), ks()
        a, m = Keys(ka), Keys(km)
        return {
            "attn_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"wq": mat(a(), (d, h * hd)), "wk": mat(a(), (d, kvh * hd)),
                     "wv": mat(a(), (d, kvh * hd)),
                     "wo": mat(a(), (h * hd, d), out_scale)},
            "mlp_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "mlp": {"w_gate": mat(m(), (d, f)), "w_up": mat(m(), (d, f)),
                    "w_down": mat(m(), (f, d), out_scale)},
        }

    ks = Keys(key)
    table = trunc_normal(ks(), (padded_vocab(cfg), d), 1.0 / math.sqrt(d), bf16)
    layers = jax.vmap(block)(jax.random.split(ks(), n))
    return {"embed": {"table": table}, "layers": layers,
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, S, heads, D); rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, lowp):
    """Causal softmax attention; query head h reads key/value head h // G."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    q = q * D**-0.5
    qb = min(S, Q_BLOCK)

    @jax.checkpoint
    def rows(qi, kk, vv, start):
        s = dot("bqhd,bkhd->bhqk", qi, kk, lowp)
        mask = (start + jnp.arange(qb))[:, None] >= jnp.arange(kk.shape[1])[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return dot("bhqk,bkhd->bqhd", p, vv, lowp)

    # query rows [i, i + qb) see keys [0, i + qb)
    return jnp.concatenate([rows(q[:, i:i + qb], k[:, :i + qb], v[:, :i + qb], i)
                            for i in range(0, S, qb)], axis=1)


def layer(cfg, lowp, h, lp):
    d, nh, kvh, hd, _ = _dims(cfg)
    eps, theta = as_run(cfg, "rms_norm_eps"), cfg["rope_theta"]
    B, S, _ = h.shape
    a = lp["attn"]
    x = rmsnorm(h, lp["attn_norm"]["scale"], eps)
    q = dot("bsd,de->bse", x, a["wq"], lowp).reshape(B, S, nh, hd)
    k = dot("bsd,de->bse", x, a["wk"], lowp).reshape(B, S, kvh, hd)
    v = dot("bsd,de->bse", x, a["wv"], lowp).reshape(B, S, kvh, hd)
    o = attention(rope(q, theta), rope(k, theta), v, lowp)
    h = h + dot("bse,ed->bsd", o.reshape(B, S, nh * hd), a["wo"], lowp)
    m = lp["mlp"]
    x = rmsnorm(h, lp["mlp_norm"]["scale"], eps)
    gate = dot("bsd,df->bsf", x, m["w_gate"], lowp)
    up = dot("bsd,df->bsf", x, m["w_up"], lowp)
    return h + dot("bsf,fd->bsd", jax.nn.silu(gate) * up, m["w_down"], lowp), None


def loss(cfg: dict, lowp: bool, params, batch):
    """Mean cross entropy of each next token, plus z_loss * mean(lse^2).
    ``params`` are float32."""
    table = params["embed"]["table"]
    h = table[batch["tokens"]]
    h, _ = jax.lax.scan(jax.checkpoint(functools.partial(layer, cfg, lowp)),
                        h, params["layers"])
    h = rmsnorm(h, params["final_norm"]["scale"], as_run(cfg, "rms_norm_eps"))
    w = table[: cfg["vocab_size"]]
    hs = h.reshape(LOSS_CHUNKS, -1, h.shape[-1])
    ls = batch["labels"].reshape(LOSS_CHUNKS, -1)

    @jax.checkpoint
    def chunk(hc, lc):
        logits = dot("nd,vd->nv", hc, w, lowp)
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = lse - jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(nll), jnp.sum(lse * lse)

    nll, z = 0.0, 0.0
    for i in range(LOSS_CHUNKS):
        a, b = chunk(hs[i], ls[i])
        nll, z = nll + a, z + b
    n = ls.size
    return nll / n + cfg["z_loss"] * z / n
