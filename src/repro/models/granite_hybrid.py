"""granite-4.0-h (HF ``GraniteMoeHybrid`` without experts): Mamba-2 layers
and GQA layers in the published order (``cfg.layer_types``), each mixer and
each SwiGLU MLP behind its own pre-norm, with granite's multipliers:

  h = E[tokens] * embedding_multiplier
  per layer: h += residual_multiplier * mixer(rmsnorm(h))
             h += residual_multiplier * mlp(rmsnorm(h))
  logits = rmsnorm(h) @ E^T / logits_scaling

The Mamba-2 mixer is ``mamba2.mamba_seq`` (its chunked SSD scan at
``cfg.ssm.chunk``); the attention mixer takes the dense family's
projections and ``attention.flash_attention`` (the Pallas flash kernels on
a TPU), with no positional encoding (the family's attention is NoPE,
``position_embedding_type: nope``) and scores scaled by
``cfg.attention_multiplier``. Each run of layers of one kind is
one ``scan_layers`` over that run's stacked weights (``params["layers"]``,
one stack per run: slices of one stack per kind would cost the backward
pass a copy of their gradients). Name scopes
``mamba_mixer``, ``ssd_scan`` and ``attention`` split a profile by kind.

Serving keeps K/V per attention layer, and SSM state plus the conv tail per
Mamba layer; decode steps both in the published order.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import losses
from repro.models import mamba2
from repro.models import module as nn
from repro.models import transformer as tfm
from repro.models.attention import decode_attention, flash_attention
from repro.models.model_api import Model, _input_specs, register_family
from repro.sharding.plan import ShardingPlan

Params = Dict[str, Any]
KINDS = ("mamba", "attention")


def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    kinds = tuple(cfg.layer_types[: cfg.n_layers])
    if len(kinds) != cfg.n_layers or set(kinds) - set(KINDS):
        raise ValueError(f"{cfg.name}: layer_types {cfg.layer_types} do not give "
                         f"{cfg.n_layers} layers of kinds {KINDS}")
    return kinds


def runs(cfg: ModelConfig) -> List[Tuple[str, int, int]]:
    """(kind, start, stop) of each run of consecutive layers of one kind,
    start and stop counting the layers of that kind before it (the rows of
    that kind's caches)."""
    out, seen = [], dict.fromkeys(KINDS, 0)
    for kind in layer_kinds(cfg):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, seen[kind], seen[kind] + 1))
        seen[kind] += 1
    return out


def _counts(cfg: ModelConfig) -> Dict[str, int]:
    kinds = layer_kinds(cfg)
    return {k: kinds.count(k) for k in KINDS}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_mamba_layer(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    return {
        "mixer": mamba2.init_mamba_block(cfg, kg()),
        "mlp_norm": nn.rmsnorm_init(cfg.d_model),
        "mlp": tfm.init_mlp_layer(cfg, kg()),
    }


INITS = {"mamba": init_mamba_layer, "attention": tfm.init_block}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    params: Params = {"embed": nn.embedding_init(kg(), cfg.padded_vocab, cfg.d_model)}
    params["layers"] = [nn.stack_layer_init(functools.partial(INITS[kind], cfg), kg(), stop - start)
                        for kind, start, stop in runs(cfg)]
    params["final_norm"] = nn.rmsnorm_init(cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _scaled(x: jax.Array, m: float) -> jax.Array:
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def _mlp_residual(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array, lp: Params):
    m = tfm._mlp(cfg, lp["mlp"], tfm._norm(cfg, lp["mlp_norm"], x), plan)
    return plan.act(x + _scaled(plan.act(m, "hidden"), cfg.residual_multiplier), "hidden")


def _mixer_residual(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array, y: jax.Array):
    return x + _scaled(plan.act(y, "hidden"), cfg.residual_multiplier)


def _state0(cfg: ModelConfig, batch: int) -> jax.Array:
    _, H, P, N = mamba2._inner(cfg)
    return jnp.zeros((batch, H, P, N), jnp.float32)


def mamba_layer(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array, lp: Params):
    """One Mamba-2 layer and its MLP; also returns the final SSM state and conv tail."""
    with jax.named_scope("mamba_mixer"):
        y, state, tail = mamba2.mamba_seq(cfg, lp["mixer"], x, plan, _state0(cfg, x.shape[0]))
    return _mlp_residual(cfg, plan, _mixer_residual(cfg, plan, x, y), lp), (state, tail)


def attention_layer(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array, lp: Params):
    """One attention layer and its MLP; also returns its K and V for a cache."""
    B, S, _ = x.shape
    with jax.named_scope("attention"):
        q, k, v = tfm._qkv(cfg, lp["attn"], tfm._norm(cfg, lp["attn_norm"], x), plan)
        out = flash_attention(q, k, v, causal=True, block_k=cfg.attn_block_k,
                              scale=cfg.attention_multiplier, plan=plan)
        y = nn.dense_apply({"w": lp["attn"]["wo"]}, plan.act(out, "heads").reshape(B, S, -1))
    x = _mlp_residual(cfg, plan, _mixer_residual(cfg, plan, x, y), lp)
    return x, (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))


LAYERS = {"mamba": mamba_layer, "attention": attention_layer}
CACHES = {"mamba": ("ssm", "conv"), "attention": ("attn_k", "attn_v")}  # what each layer keeps


def embed(cfg: ModelConfig, params: Params, tokens: jax.Array, plan: ShardingPlan):
    h = nn.embedding_apply(params["embed"], tokens)
    return plan.act(_scaled(h, cfg.embedding_multiplier), "hidden")


def logits_fn(cfg: ModelConfig, params: Params, h: jax.Array, plan: ShardingPlan):
    return _scaled(tfm.logits_fn(cfg, params, h, plan), 1.0 / cfg.logits_scaling)


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, plan: ShardingPlan):
    """Token ids (B, S) -> logits (B, S, V)."""
    h = embed(cfg, params, tokens, plan)
    for (kind, _, _), layers in zip(runs(cfg), params["layers"]):
        body = functools.partial(LAYERS[kind], cfg, plan)
        h = nn.scan_layers(lambda x, lp, body=body: body(x, lp)[0], h, layers, remat=cfg.remat)
    return plan.act(logits_fn(cfg, params, h, plan), "logits")


# ---------------------------------------------------------------------------
# serving: K/V for the attention layers, SSM state and conv tail for Mamba
# ---------------------------------------------------------------------------


def cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    d_inner, H, P, N = mamba2._inner(cfg)
    counts = _counts(cfg)
    kv = (counts["attention"], batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim)
    return {
        "ssm": jax.ShapeDtypeStruct((counts["mamba"], batch, H, P, N), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (counts["mamba"], batch, cfg.ssm.d_conv - 1, d_inner + 2 * N), jnp.bfloat16),
        "attn_k": jax.ShapeDtypeStruct(kv, jnp.bfloat16),
        "attn_v": jax.ShapeDtypeStruct(kv, jnp.bfloat16),
    }


def _cache(plan: ShardingPlan, parts: Dict[str, list]) -> Dict[str, jax.Array]:
    cat = {k: jnp.concatenate(v, axis=0) for k, v in parts.items()}
    return {"ssm": plan.act(cat["ssm"], "state"), "conv": cat["conv"],
            "attn_k": plan.act(cat["attn_k"], "cache"),
            "attn_v": plan.act(cat["attn_v"], "cache")}


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, plan: ShardingPlan):
    """Forward over the prompt; returns (last-position logits (B, V), cache)."""
    h = embed(cfg, params, tokens, plan)
    parts: Dict[str, list] = {"ssm": [], "conv": [], "attn_k": [], "attn_v": []}
    for (kind, _, _), layers in zip(runs(cfg), params["layers"]):
        h, kept = jax.lax.scan(functools.partial(LAYERS[kind], cfg, plan), h, layers)
        for name, a in zip(CACHES[kind], kept):
            parts[name].append(a)
    last = logits_fn(cfg, params, h[:, -1:, :], plan)[:, 0, :]
    return plan.act(last, "last_logits"), _cache(plan, parts)


def decode_step(cfg, params, token, cache, pos, plan: ShardingPlan):
    """One token (B,) against the cache at position ``pos``."""
    B = token.shape[0]
    pos_arr = jnp.asarray(pos, jnp.int32)
    x = _scaled(nn.embedding_apply(params["embed"], token[:, None])[:, 0, :],
                cfg.embedding_multiplier)

    def mamba(x, layer_in):
        lp, st, tail = layer_in
        y, st, tail = mamba2.mamba_step(cfg, lp["mixer"], x, st, tail)
        x = _mlp_residual(cfg, plan, _mixer_residual(cfg, plan, x, y), lp)
        return x, (st, tail)

    def attention(x, layer_in):
        lp, kc, vc = layer_in
        xs = x[:, None, :]
        q, k, v = tfm._qkv(cfg, lp["attn"], tfm._norm(cfg, lp["attn_norm"], xs), plan)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos_arr, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos_arr, 1)
        out = decode_attention(q, kc, vc, kv_len=pos_arr + 1,
                               scale=cfg.attention_multiplier)
        y = nn.dense_apply({"w": lp["attn"]["wo"]}, out.reshape(B, 1, -1))
        xs = _mlp_residual(cfg, plan, _mixer_residual(cfg, plan, xs, y), lp)
        return xs[:, 0, :], (kc, vc)

    steps = {"mamba": mamba, "attention": attention}
    parts: Dict[str, list] = {"ssm": [], "conv": [], "attn_k": [], "attn_v": []}
    for (kind, start, stop), layers in zip(runs(cfg), params["layers"]):
        names = CACHES[kind]
        x, kept = jax.lax.scan(steps[kind], x, (layers, *(cache[n][start:stop] for n in names)))
        for name, a in zip(names, kept):
            parts[name].append(a)
    logits = logits_fn(cfg, params, x[:, None, :], plan)[:, 0, :]
    return plan.act(logits, "last_logits"), _cache(plan, parts)


@register_family("granite_hybrid")
def _build(cfg: ModelConfig) -> Model:
    def loss(params, batch, plan: ShardingPlan):
        logits = forward(cfg, params, batch["tokens"], plan)
        return losses.softmax_cross_entropy(logits, batch["labels"])

    return Model(
        cfg=cfg,
        init=lambda key: init_params(cfg, key),
        loss=loss,
        prefill=lambda params, batch, plan: prefill(cfg, params, batch["tokens"], plan),
        decode=lambda params, batch, cache, pos, plan: decode_step(
            cfg, params, batch["token"], cache, pos, plan),
        cache_spec=lambda b, s: cache_spec(cfg, b, s),
        input_specs=lambda suite: _input_specs(cfg, suite),
    )
