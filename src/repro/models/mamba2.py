"""Mamba2 (SSD) blocks and the zamba2-7b hybrid (Mamba2 backbone + one
*shared* GQA attention block applied before every ``attn_every``-th layer).

SSD recurrence (per head h, state h_t in R^{P x N}, scalar decay a_t):
  h_t = a_t * h_{t-1} + (dt_t x_t) outer B_t
  y_t = h_t @ C_t + D * x_t
Training uses the chunked form (bounded pairwise decays, scan over chunks);
decode carries (B, H, P, N) state + a (B, d_conv-1, conv_channels) conv tail,
so serving cost is sequence-independent -> zamba2 runs ``long_500k``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import losses
from repro.models import module as nn
from repro.models import transformer as tfm
from repro.models.attention import decode_attention
from repro.models.model_api import Model, _input_specs, register_family
from repro.sharding.plan import ShardingPlan

Params = Dict[str, Any]

# step sizes at initialisation (mamba_ssm's dt_min, dt_max, dt_init_floor)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


# ---------------------------------------------------------------------------
# chunked SSD core
# ---------------------------------------------------------------------------


def ssd_chunked(
    x: jax.Array,  # (B, T, H, P) inner activations (dt-scaled outside)
    dt: jax.Array,  # (B, T, H) softplus'd step sizes
    A: jax.Array,  # (H,) negative decay rates
    Bm: jax.Array,  # (B, T, N) input projections (single group)
    Cm: jax.Array,  # (B, T, N)
    state0: jax.Array,  # (B, H, P, N)
    chunk: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,T,H,P) f32, final state)."""
    B_, T, H, P = x.shape
    N = Bm.shape[-1]
    assert T % chunk == 0
    n = T // chunk

    la_full = dt * A[None, None, :]  # (B,T,H) log-decay per step, <= 0
    xr = x.astype(jnp.float32).reshape(B_, n, chunk, H, P).transpose(1, 0, 3, 2, 4)
    dtr = dt.astype(jnp.float32).reshape(B_, n, chunk, H).transpose(1, 0, 3, 2)
    lar = la_full.astype(jnp.float32).reshape(B_, n, chunk, H).transpose(1, 0, 3, 2)
    Br = Bm.astype(jnp.float32).reshape(B_, n, chunk, N).transpose(1, 0, 2, 3)
    Cr = Cm.astype(jnp.float32).reshape(B_, n, chunk, N).transpose(1, 0, 2, 3)
    # xr/dtr/lar: (n,B,H,C[,P]); Br/Cr: (n,B,C,N)

    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))  # s <= t inclusive

    def body(S, inputs):
        xb, dtb, lab, Bb, Cb = inputs
        cla = jnp.cumsum(lab, axis=-1)  # (B,H,C) inclusive
        # pairwise decay exp(cla_t - cla_s) for s<=t (bounded <= 1)
        diff = cla[:, :, :, None] - cla[:, :, None, :]  # (B,H,C,C)
        decay = jnp.exp(jnp.where(tri[None, None], diff, -jnp.inf))
        cb = jnp.einsum("btn,bsn->bts", Cb, Bb)  # (B,C,C)
        scores = decay * cb[:, None, :, :]  # (B,H,C,C)
        xdt = xb * dtb[..., None]  # dt-weighted inputs
        y = jnp.einsum("bhts,bhsp->bhtp", scores, xdt)
        # cross-chunk: y += exp(cla_t) * (C_t . S)
        y = y + jnp.exp(cla)[..., None] * jnp.einsum("bhpn,btn->bhtp", S, Cb).transpose(
            0, 1, 2, 3
        )
        # state: S' = exp(cla[-1]) S + sum_s exp(cla[-1]-cla_s) (dt_s x_s) outer B_s
        last = cla[:, :, -1:]  # (B,H,1)
        w = jnp.exp(last - cla)  # (B,H,C)
        S_new = jnp.exp(last)[..., None] * S + jnp.einsum(
            "bhsp,bsn,bhs->bhpn", xdt, Bb, w
        )
        return S_new, y

    state, ys = jax.lax.scan(body, state0.astype(jnp.float32), (xr, dtr, lar, Br, Cr))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(B_, T, H, P)
    return y, state


def ssd_step(x, dt, A, Bm, Cm, state):
    """Single step. x:(B,H,P), dt:(B,H), Bm/Cm:(B,N), state (B,H,P,N)."""
    la = dt * A[None, :]
    a = jnp.exp(la.astype(jnp.float32))  # (B,H)
    xdt = (x * dt[..., None]).astype(jnp.float32)
    upd = jnp.einsum("bhp,bn->bhpn", xdt, Bm.astype(jnp.float32))
    state = a[..., None, None] * state + upd
    y = jnp.einsum("bhpn,bn->bhp", state, Cm.astype(jnp.float32))
    return y, state


# ---------------------------------------------------------------------------
# mamba2 block
# ---------------------------------------------------------------------------


def _inner(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    H = d_inner // s.head_dim
    return d_inner, H, s.head_dim, s.state_dim


def init_mamba_block(cfg: ModelConfig, key: jax.Array) -> Params:
    """Input norm and Mamba-2 mixer. A and the step sizes are drawn as
    ``mamba_ssm`` draws them: A ~ U[1, 16], and the dt bias is the inverse
    softplus of dt, log-uniform in [1e-3, 1e-1], so that the slowest heads
    carry their state across many chunks."""
    kg = nn.KeyGen(key)
    d = cfg.d_model
    d_inner, H, P, N = _inner(cfg)
    s = cfg.ssm
    conv_ch = d_inner + 2 * N  # x, B, C go through the short conv
    w_in = nn.fan_in_init(kg(), (d, 2 * d_inner + 2 * N + H), jnp.bfloat16)  # [z, x, B, C, dt]
    conv_w = nn.trunc_normal(kg(), (s.d_conv, conv_ch), 0.1, jnp.bfloat16)
    A = jax.random.uniform(kg(), (H,), jnp.float32, 1.0, 16.0)
    u = jax.random.uniform(kg(), (H,), jnp.float32)
    dt = jnp.maximum(jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)),
                     DT_FLOOR)
    return {
        "norm": nn.rmsnorm_init(d),
        "w_in": w_in,
        "conv_w": conv_w,
        "conv_b": jnp.zeros((conv_ch,), jnp.bfloat16),
        "A_log": jnp.log(A),  # A = -exp(A_log)
        "D": jnp.ones((H,), jnp.float32),
        # softplus(bias) = dt; kept under "bias" so that no optimizer decays it
        "dt": {"bias": dt + jnp.log(-jnp.expm1(-dt))},
        "out_norm": nn.rmsnorm_init(d_inner),
        "w_out": nn.fan_in_init(
            kg(), (d_inner, d), jnp.bfloat16, scale=1.0 / (2 * cfg.n_layers) ** 0.5
        ),
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    d_inner, H, P, N = _inner(cfg)
    z, xin, Bc, Cc, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N], axis=-1
    )
    return z, xin, Bc, Cc, dt


def _causal_conv_seq(w, b, x, tail: Optional[jax.Array] = None):
    """Depthwise causal conv along T. x: (B,T,C); w: (K,C). Returns (y, new_tail)."""
    K = w.shape[0]
    pad = (
        jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype) if tail is None else tail
    )
    xp = jnp.concatenate([pad, x], axis=1)
    # sum_k w[k] * x[t - (K-1) + k]
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K)
    )
    y = y + b[None, None, :]
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype), xp[:, -(K - 1) :, :]


def _gated_out(cfg: ModelConfig, p: Params, y: jax.Array, z: jax.Array) -> jax.Array:
    """Output projection of y gated before the norm, as Mamba-2's
    RMSNormGated(norm_before_gate=False): rmsnorm(y * silu(z)) @ W_out."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    return nn.dense_apply({"w": p["w_out"]},
                          nn.rmsnorm_apply(p["out_norm"], g, eps=cfg.norm_eps))


def mamba_seq(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,  # (B,T,d)
    plan: ShardingPlan,
    state0: jax.Array,
    conv_tail: Optional[jax.Array] = None,
):
    B, T, d = x.shape
    d_inner, H, P, N = _inner(cfg)
    xn = nn.rmsnorm_apply(p["norm"], x, eps=cfg.norm_eps)
    proj = nn.dense_apply({"w": p["w_in"]}, xn)
    z, xin, Bc, Cc, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_out, new_tail = _causal_conv_seq(p["conv_w"], p["conv_b"], conv_in, conv_tail)
    xin, Bc, Cc = jnp.split(conv_out, [d_inner, d_inner + N], axis=-1)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt"]["bias"][None, None, :])
    A = -jnp.exp(p["A_log"])
    xh = plan.act(xin.reshape(B, T, H, P), "heads")
    with jax.named_scope("ssd_scan"):
        y, state = ssd_chunked(xh, dtv, A, Bc, Cc, state0, chunk=cfg.ssm.chunk)
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    return _gated_out(cfg, p, y.reshape(B, T, d_inner), z), state, new_tail


def mamba_step(cfg: ModelConfig, p: Params, x, state, conv_tail):
    """x: (B,d). conv_tail: (B, K-1, C)."""
    B, d = x.shape
    d_inner, H, P, N = _inner(cfg)
    xn = nn.rmsnorm_apply(p["norm"], x, eps=cfg.norm_eps)
    proj = nn.dense_apply({"w": p["w_in"]}, xn)
    z, xin, Bc, Cc, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)  # (B,C)
    window = jnp.concatenate([conv_tail, conv_in[:, None, :]], axis=1)  # (B,K,C)
    w = p["conv_w"]
    y = jnp.einsum("bkc,kc->bc", window, w.astype(window.dtype)) + p["conv_b"]
    y = jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype)
    xin, Bc, Cc = jnp.split(y, [d_inner, d_inner + N], axis=-1)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt"]["bias"][None, :])
    A = -jnp.exp(p["A_log"])
    yh, state = ssd_step(xin.reshape(B, H, P), dtv, A, Bc, Cc, state)
    yh = yh + p["D"][None, :, None] * xin.reshape(B, H, P).astype(jnp.float32)
    return _gated_out(cfg, p, yh.reshape(B, d_inner), z), state, window[:, 1:, :]


# ---------------------------------------------------------------------------
# zamba2 hybrid assembly
# ---------------------------------------------------------------------------


def _group_sizes(cfg: ModelConfig):
    """Layer groups: shared attention applied before each group."""
    k = cfg.attn_every
    n = cfg.n_layers
    if k <= 0:
        return [n]
    full, rem = divmod(n, k)
    return [k] * full + ([rem] if rem else [])


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    params: Params = {
        "embed": nn.embedding_init(kg(), cfg.padded_vocab, cfg.d_model),
        "layers": nn.stack_layer_init(
            functools.partial(init_mamba_block, cfg), kg(), cfg.n_layers
        ),
        "final_norm": nn.rmsnorm_init(cfg.d_model),
        "lm_head": {"w_lm": nn.fan_in_init(kg(), (cfg.d_model, cfg.padded_vocab), jnp.bfloat16)},
    }
    if cfg.attn_every:
        params["shared_attn"] = tfm.init_block(cfg, kg())
    return params


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, plan: ShardingPlan):
    B, T = tokens.shape
    d_inner, H, P, N = _inner(cfg)
    h = nn.embedding_apply(params["embed"], tokens)
    h = plan.act(h, "hidden")
    state0 = jnp.zeros((B, H, P, N), jnp.float32)

    def mamba_body(x, lp):
        y, _, _ = mamba_seq(cfg, lp, x, plan, state0)
        return plan.act(x + y, "hidden")

    start = 0
    for g, size in enumerate(_group_sizes(cfg)):
        if cfg.attn_every:
            h = tfm.block_fwd(cfg, plan, h, params["shared_attn"])
        group = nn.slice_layers(params["layers"], start, start + size)
        h = nn.scan_layers(mamba_body, h, group, remat=cfg.remat)
        start += size
    logits = tfm.logits_fn(cfg, params, h, plan)
    return plan.act(logits, "logits")


def cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    d_inner, H, P, N = _inner(cfg)
    s = cfg.ssm
    conv_ch = d_inner + 2 * N
    L = cfg.n_layers
    spec = {
        "ssm": jax.ShapeDtypeStruct((L, batch, H, P, N), jnp.float32),
        "conv": jax.ShapeDtypeStruct((L, batch, s.d_conv - 1, conv_ch), jnp.bfloat16),
    }
    if cfg.attn_every:
        n_apps = len(_group_sizes(cfg))
        hd = cfg.resolved_head_dim
        spec["attn_k"] = jax.ShapeDtypeStruct(
            (n_apps, batch, max_len, cfg.n_kv_heads, hd), jnp.bfloat16
        )
        spec["attn_v"] = jax.ShapeDtypeStruct(
            (n_apps, batch, max_len, cfg.n_kv_heads, hd), jnp.bfloat16
        )
    return spec


def _attn_prefill_block(cfg, lp, x, plan, positions):
    """Shared-attn block forward that also returns rope'd K/V for the cache."""
    B, S, _ = x.shape
    xn = tfm._norm(cfg, lp["attn_norm"], x)
    q, k, v = tfm._qkv(cfg, lp["attn"], xn, plan)
    q = nn.apply_rope(q, positions, cfg.rope_theta)
    kr = nn.apply_rope(k, positions, cfg.rope_theta)
    out = tfm.xla_flash_attention(
        q, kr, v, causal=True, block_k=cfg.attn_block_k, plan=plan
    )
    x = x + nn.dense_apply({"w": lp["attn"]["wo"]}, out.reshape(B, S, -1))
    x = x + tfm._mlp(cfg, lp["mlp"], tfm._norm(cfg, lp["mlp_norm"], x), plan)
    return plan.act(x, "hidden"), kr.astype(jnp.bfloat16), v.astype(jnp.bfloat16)


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, plan: ShardingPlan):
    B, T = tokens.shape
    d_inner, H, P, N = _inner(cfg)
    h = nn.embedding_apply(params["embed"], tokens)
    h = plan.act(h, "hidden")
    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    positions = jnp.arange(T)

    def mamba_body(x, lp):
        y, state, tail = mamba_seq(cfg, lp, x, plan, state0)
        return plan.act(x + y, "hidden"), (state, tail)

    ssm_states, conv_tails, ks, vs = [], [], [], []
    start = 0
    for size in _group_sizes(cfg):
        if cfg.attn_every:
            h, kr, v = _attn_prefill_block(cfg, params["shared_attn"], h, plan, positions)
            ks.append(kr)
            vs.append(v)
        group = nn.slice_layers(params["layers"], start, start + size)

        def step(c, lp):
            c, extras = mamba_body(c, lp)
            return c, extras

        h, (st, tl) = jax.lax.scan(step, h, group)
        ssm_states.append(st)
        conv_tails.append(tl)
        start += size

    cache = {
        "ssm": plan.act(jnp.concatenate(ssm_states, axis=0), "state"),
        "conv": jnp.concatenate(conv_tails, axis=0),
    }
    if cfg.attn_every:
        cache["attn_k"] = plan.act(jnp.stack(ks), "cache")
        cache["attn_v"] = plan.act(jnp.stack(vs), "cache")
    logits = tfm.logits_fn(cfg, params, h[:, -1:, :], plan)[:, 0, :]
    return plan.act(logits, "last_logits"), cache


def decode_step(cfg, params, token, cache, pos, plan: ShardingPlan):
    B = token.shape[0]
    pos_arr = jnp.asarray(pos, jnp.int32)
    x = nn.embedding_apply(params["embed"], token[:, None])[:, 0, :]

    def mamba_scan(x, layer_in):
        lp, st, tail = layer_in
        y, st2, tail2 = mamba_step(cfg, lp, x, st, tail)
        return x + y, (st2, tail2)

    new_k, new_v = [], []
    start = 0
    sizes = _group_sizes(cfg)
    ssm_out = []
    conv_out = []
    for g, size in enumerate(sizes):
        if cfg.attn_every:
            lp = params["shared_attn"]
            xs = x[:, None, :]
            xn = tfm._norm(cfg, lp["attn_norm"], xs)
            q, k, v = tfm._qkv(cfg, lp["attn"], xn, plan)
            q = nn.apply_rope(q, pos_arr[None], cfg.rope_theta)
            k = nn.apply_rope(k, pos_arr[None], cfg.rope_theta)
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache["attn_k"][g], k.astype(jnp.bfloat16), pos_arr, 1
            )
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache["attn_v"][g], v.astype(jnp.bfloat16), pos_arr, 1
            )
            out = decode_attention(q, kc, vc, kv_len=pos_arr + 1)
            xs = xs + nn.dense_apply({"w": lp["attn"]["wo"]}, out.reshape(B, 1, -1))
            xs = xs + tfm._mlp(cfg, lp["mlp"], tfm._norm(cfg, lp["mlp_norm"], xs), plan)
            x = xs[:, 0, :]
            new_k.append(kc)
            new_v.append(vc)
        group = nn.slice_layers(params["layers"], start, start + size)
        st = jax.lax.dynamic_slice_in_dim(cache["ssm"], start, size, 0)
        tail = jax.lax.dynamic_slice_in_dim(cache["conv"], start, size, 0)
        x, (st2, tail2) = jax.lax.scan(mamba_scan, x, (group, st, tail))
        ssm_out.append(st2)
        conv_out.append(tail2)
        start += size

    new_cache = {
        "ssm": plan.act(jnp.concatenate(ssm_out, axis=0), "state"),
        "conv": jnp.concatenate(conv_out, axis=0),
    }
    if cfg.attn_every:
        new_cache["attn_k"] = plan.act(jnp.stack(new_k), "cache")
        new_cache["attn_v"] = plan.act(jnp.stack(new_v), "cache")
    logits = tfm.logits_fn(cfg, params, x[:, None, :], plan)[:, 0, :]
    return plan.act(logits, "last_logits"), new_cache


@register_family("hybrid")
def _build_hybrid(cfg: ModelConfig) -> Model:
    def loss(params, batch, plan: ShardingPlan):
        logits = forward(cfg, params, batch["tokens"], plan)
        return losses.softmax_cross_entropy(logits, batch["labels"])

    return Model(
        cfg=cfg,
        init=lambda key: init_params(cfg, key),
        loss=loss,
        prefill=lambda params, batch, plan: prefill(cfg, params, batch["tokens"], plan),
        decode=lambda params, batch, cache, pos, plan: decode_step(
            cfg, params, batch["token"], cache, pos, plan
        ),
        cache_spec=lambda b, s: cache_spec(cfg, b, s),
        input_specs=lambda suite: _input_specs(cfg, suite),
    )


register_family("ssm")(_build_hybrid)  # pure-mamba configs reuse the hybrid path
