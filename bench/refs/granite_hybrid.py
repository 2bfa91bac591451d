"""Plain reference of granite-4.0-h (HF ``GraniteMoeHybrid`` with no
experts; its Mamba-2 mixer as ``mamba_ssm`` publishes it): the layers of
``layer_types`` in order, each a pre-norm mixer and a pre-norm SwiGLU MLP,
with granite's four multipliers:

  h = E[tokens] * embedding_multiplier
  per layer: h += residual_multiplier * mixer(rmsnorm(h))
             h += residual_multiplier * W_down(silu(W_gate x) * W_up x),  x = rmsnorm(h)
  logits = rmsnorm(h) E^T / logits_scaling

Mamba-2 mixer, one group: [z | xBC | dt] = x W_in; xBC = silu(causal
depthwise conv4(xBC) + b), split into x (heads x head_dim), B and C (d_state
each); dt = softplus(dt + dt_bias), A = -exp(A_log);
s_t = exp(dt_t A) s_{t-1} + dt_t x_t (x) B_t,  y_t = s_t C_t + D x_t;
out = rmsnorm(y * silu(z)) W_out (the gate before the norm). The state is
run one token at a time, the recurrence as written, not the chunked form
the program computes; the scan is checkpointed every ``SCAN_BLOCK`` tokens
so that its backward pass holds one block's states at a time.

Attention: grouped-query causal softmax with no positional encoding
(``position_embedding_type: nope``), scores scaled by
``attention_multiplier``, computed exactly one block of query rows at a time.

Loss: mean next-token cross entropy plus ``z_loss`` * mean(logsumexp^2),
as the program's, over the ``vocab_size`` real rows of the tied table.

Everything is float32 at HIGHEST precision from weights stored as the
configuration states them; each layer is recomputed in the backward pass.
Departures from the published description, each because the program does
the same: the embedding table has its rows rounded up to a multiple of 16
(never indexed); the z-loss (``assumed`` in the file); A and the dt bias
are drawn as ``mamba_ssm`` draws them, the conv weights from a truncated
normal and the conv bias zero (``assumed``). Nothing here imports the program: the
weights are made from the seed's key in the program's order of keys.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from refs.common import Keys, dot, trunc_normal
from refs.dense import LOSS_CHUNKS, attention, padded_vocab, rmsnorm

SCAN_BLOCK = 64  # tokens of the recurrence between checkpoints
OUT_LEAF = "['embed']['table']"  # the output layer (tied), by its path in the weights
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4  # mamba_ssm's step sizes at init


def runs(cfg: dict) -> list:
    """(kind, length) of each run of consecutive layers of one kind."""
    out = []
    for kind in cfg["layer_types"][: cfg["num_hidden_layers"]]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [tuple(r) for r in out]


def _dims(cfg):
    d = cfg["hidden_size"]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return d, cfg["mamba_expand"] * d, H, P, N


def init(cfg: dict, key):
    """Weights from the key, split in the program's order: the embedding,
    then one key for each run of layers of one kind, split once per layer
    of the run; ``layers`` holds each run's weights stacked."""
    d, di, H, P, N = _dims(cfg)
    h, kvh, f = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["intermediate_size"]
    hd = d // h
    n = cfg["num_hidden_layers"]
    out_scale = 1.0 / math.sqrt(2 * n)
    bf16, f32 = jnp.bfloat16, jnp.float32
    ones = lambda m: {"scale": jnp.ones((m,), f32)}  # noqa: E731

    def mat(k, shape, scale=1.0):  # fan-in scaled
        return trunc_normal(k, shape, scale / math.sqrt(shape[0]), bf16)

    def mlp(k):
        m = Keys(k)
        return {"w_gate": mat(m(), (d, f)), "w_up": mat(m(), (d, f)),
                "w_down": mat(m(), (f, d), out_scale)}

    def mamba_layer(k):
        ks = Keys(k)
        km, kf = ks(), ks()
        m = Keys(km)
        conv_ch = di + 2 * N
        w_in = mat(m(), (d, 2 * di + 2 * N + H))
        conv_w = trunc_normal(m(), (cfg["mamba_d_conv"], conv_ch), 0.1, bf16)
        A = jax.random.uniform(m(), (H,), f32, 1.0, 16.0)
        u = jax.random.uniform(m(), (H,), f32)
        dt = jnp.maximum(jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)),
                         DT_FLOOR)
        mixer = {"norm": ones(d), "w_in": w_in, "conv_w": conv_w,
                 "conv_b": jnp.zeros((conv_ch,), bf16), "A_log": jnp.log(A),
                 "D": jnp.ones((H,), f32), "dt": {"bias": dt + jnp.log(-jnp.expm1(-dt))},
                 "out_norm": ones(di), "w_out": mat(m(), (di, d), out_scale)}
        return {"mixer": mixer, "mlp_norm": ones(d), "mlp": mlp(kf)}

    def attention_layer(k):
        ks = Keys(k)
        ka, kf = ks(), ks()
        a = Keys(ka)
        return {"attn_norm": ones(d),
                "attn": {"wq": mat(a(), (d, h * hd)), "wk": mat(a(), (d, kvh * hd)),
                         "wv": mat(a(), (d, kvh * hd)), "wo": mat(a(), (h * hd, d), out_scale)},
                "mlp_norm": ones(d), "mlp": mlp(kf)}

    make = {"mamba": mamba_layer, "attention": attention_layer}
    ks = Keys(key)
    table = trunc_normal(ks(), (padded_vocab(cfg), d), 1.0 / math.sqrt(d), bf16)
    layers = [jax.vmap(make[kind])(jax.random.split(ks(), length)) for kind, length in runs(cfg)]
    return {"embed": {"table": table}, "layers": layers, "final_norm": ones(d)}


def ssd_recurrence(x, dt, A, Bm, Cm, lowp):
    """y_t = s_t C_t with s_t = exp(dt_t A) s_{t-1} + dt_t x_t (x) B_t, s_0 = 0.
    x: (B, T, H, P); dt: (B, T, H); Bm, Cm: (B, T, N). Returns (B, T, H, P)."""
    Bsz, T, H, P = x.shape
    N = Bm.shape[-1]
    blk = min(T, SCAN_BLOCK)

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = jnp.exp(dtt * A)[:, :, None, None] * s + dot("bhp,bn->bhpn", xt * dtt[..., None], bt, lowp)
        return s, dot("bhpn,bn->bhp", s, ct, lowp)

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    # (T, ...) -> (T / blk, blk, ...): the outer scan keeps one state per block
    seq = lambda a: jnp.moveaxis(a, 1, 0).reshape(T // blk, blk, *a.shape[:1], *a.shape[2:])  # noqa: E731
    _, y = jax.lax.scan(block, jnp.zeros((Bsz, H, P, N), jnp.float32),
                        (seq(x), seq(dt), seq(Bm), seq(Cm)))
    return jnp.moveaxis(y.reshape(T, Bsz, H, P), 0, 1)


def mamba_mixer(cfg, lowp, x, p):
    d, di, H, P, N = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    Bsz, T, _ = x.shape
    proj = dot("btd,de->bte", rmsnorm(x, p["norm"]["scale"], eps), p["w_in"], lowp)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], proj[..., 2 * di + 2 * N:]
    K = p["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))  # causal: K - 1 zeros before
    conv = sum(xp[:, i:i + T] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    xs, Bm, Cm = xbc[..., :di].reshape(Bsz, T, H, P), xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt"]["bias"])
    y = ssd_recurrence(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, lowp)
    y = (y + p["D"][:, None] * xs).reshape(Bsz, T, di)
    g = rmsnorm(y * jax.nn.silu(z), p["out_norm"]["scale"], eps)
    return dot("bte,ed->btd", g, p["w_out"], lowp)


def attention_mixer(cfg, lowp, x, p):
    d, nh, kvh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    Bsz, S, _ = x.shape
    a = p["attn"]
    xn = rmsnorm(x, p["attn_norm"]["scale"], cfg["rms_norm_eps"])
    q = dot("bsd,de->bse", xn, a["wq"], lowp).reshape(Bsz, S, nh, hd)
    k = dot("bsd,de->bse", xn, a["wk"], lowp).reshape(Bsz, S, kvh, hd)
    v = dot("bsd,de->bse", xn, a["wv"], lowp).reshape(Bsz, S, kvh, hd)
    # no positions; refs.dense.attention scales scores by hd**-0.5, so q is
    # given attention_multiplier * sqrt(hd) (both powers of two: exact)
    o = attention(q * (cfg["attention_multiplier"] * math.sqrt(hd)), k, v, lowp)
    return dot("bse,ed->bsd", o.reshape(Bsz, S, nh * hd), a["wo"], lowp)


MIXERS = {"mamba": mamba_mixer, "attention": attention_mixer}


def layer(cfg, lowp, kind, h, lp):
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    h = h + r * MIXERS[kind](cfg, lowp, h, lp["mixer"] if kind == "mamba" else lp)
    m = lp["mlp"]
    x = rmsnorm(h, lp["mlp_norm"]["scale"], eps)
    gate = dot("bsd,df->bsf", x, m["w_gate"], lowp)
    up = dot("bsd,df->bsf", x, m["w_up"], lowp)
    return h + r * dot("bsf,fd->bsd", jax.nn.silu(gate) * up, m["w_down"], lowp), None


def loss(cfg: dict, lowp: bool, params, batch):
    """Mean cross entropy of each next token, plus z_loss * mean(lse^2).
    ``params`` are float32."""
    table = params["embed"]["table"]
    h = table[batch["tokens"]] * cfg["embedding_multiplier"]
    for (kind, _), run in zip(runs(cfg), params["layers"]):
        h, _ = jax.lax.scan(jax.checkpoint(functools.partial(layer, cfg, lowp, kind)), h, run)
    h = rmsnorm(h, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    w = table[: cfg["vocab_size"]]
    hs = h.reshape(LOSS_CHUNKS, -1, h.shape[-1])
    ls = batch["labels"].reshape(LOSS_CHUNKS, -1)

    @jax.checkpoint
    def chunk(hc, lc):
        logits = dot("nd,vd->nv", hc, w, lowp) / cfg["logits_scaling"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = lse - jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(nll), jnp.sum(lse * lse)

    nll, z = 0.0, 0.0
    for c in range(LOSS_CHUNKS):
        a, b = chunk(hs[c], ls[c])
        nll, z = nll + a, z + b
    n = ls.size
    return nll / n + cfg["z_loss"] * z / n
