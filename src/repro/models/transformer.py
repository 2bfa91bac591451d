"""Dense GQA decoder-only transformer (stablelm/qwen2/granite/llama3 + the
llava backbone). Depth is consumed with ``lax.scan`` over stacked layer params
so the lowered HLO is O(1) in layer count.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import module as nn
from repro.models.attention import decode_attention, flash_attention as xla_flash_attention
from repro.sharding.plan import ShardingPlan

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# layer init
# ---------------------------------------------------------------------------


def init_attn_layer(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p = {
        "wq": nn.fan_in_init(kg(), (d, cfg.n_heads * hd), jnp.bfloat16),
        "wk": nn.fan_in_init(kg(), (d, cfg.n_kv_heads * hd), jnp.bfloat16),
        "wv": nn.fan_in_init(kg(), (d, cfg.n_kv_heads * hd), jnp.bfloat16),
        "wo": nn.fan_in_init(
            kg(), (cfg.n_heads * hd, d), jnp.bfloat16, scale=1.0 / (2 * cfg.n_layers) ** 0.5
        ),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.bfloat16)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.bfloat16)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.bfloat16)
    return p


def init_mlp_layer(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    d, f = cfg.d_model, cfg.d_ff
    if cfg.act == "swiglu":
        return {
            "w_gate": nn.fan_in_init(kg(), (d, f), jnp.bfloat16),
            "w_up": nn.fan_in_init(kg(), (d, f), jnp.bfloat16),
            "w_down": nn.fan_in_init(
                kg(), (f, d), jnp.bfloat16, scale=1.0 / (2 * cfg.n_layers) ** 0.5
            ),
        }
    return {
        "w_up": nn.fan_in_init(kg(), (d, f), jnp.bfloat16),
        "w_down": nn.fan_in_init(
            kg(), (f, d), jnp.bfloat16, scale=1.0 / (2 * cfg.n_layers) ** 0.5
        ),
    }


def init_block(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    norm_init = nn.rmsnorm_init if cfg.norm == "rmsnorm" else nn.layernorm_init
    return {
        "attn_norm": norm_init(cfg.d_model),
        "attn": init_attn_layer(cfg, kg()),
        "mlp_norm": norm_init(cfg.d_model),
        "mlp": init_mlp_layer(cfg, kg()),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    params: Params = {
        "embed": nn.embedding_init(kg(), cfg.padded_vocab, cfg.d_model),
        "layers": nn.stack_layer_init(
            functools.partial(init_block, cfg), kg(), cfg.n_layers
        ),
        "final_norm": (nn.rmsnorm_init if cfg.norm == "rmsnorm" else nn.layernorm_init)(
            cfg.d_model
        ),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w_lm": nn.fan_in_init(kg(), (cfg.d_model, cfg.padded_vocab), jnp.bfloat16)
        }
    if cfg.n_patches:
        params["patch_proj"] = {
            "w_in": nn.fan_in_init(kg(), (cfg.d_model, cfg.d_model), jnp.bfloat16)
        }
    return params


# ---------------------------------------------------------------------------
# forward pieces
# ---------------------------------------------------------------------------


def _norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return nn.rmsnorm_apply(p, x, eps=cfg.norm_eps)
    return nn.layernorm_apply(p, x)


def _mlp(cfg: ModelConfig, p: Params, x: jax.Array, plan: ShardingPlan) -> jax.Array:
    if cfg.act == "swiglu":
        gate = nn.dense_apply({"w": p["w_gate"]}, x)
        up = nn.dense_apply({"w": p["w_up"]}, x)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    else:
        h = jax.nn.gelu(nn.dense_apply({"w": p["w_up"]}, x).astype(jnp.float32)).astype(
            x.dtype
        )
    h = plan.act(h, "ffn")
    return nn.dense_apply({"w": p["w_down"]}, h)


def _qkv(
    cfg: ModelConfig, p: Params, x: jax.Array, plan: ShardingPlan
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = nn.dense_apply({"w": p["wq"], **({"b": p["bq"]} if "bq" in p else {})}, x)
    k = nn.dense_apply({"w": p["wk"], **({"b": p["bk"]} if "bk" in p else {})}, x)
    v = nn.dense_apply({"w": p["wv"], **({"b": p["bv"]} if "bv" in p else {})}, x)
    q = plan.act(q.reshape(B, S, cfg.n_heads, hd), "heads")
    k = plan.act(k.reshape(B, S, cfg.n_kv_heads, hd), "kv_heads")
    v = plan.act(v.reshape(B, S, cfg.n_kv_heads, hd), "kv_heads")
    return q, k, v


def _attn_train(
    cfg: ModelConfig, p: Params, x: jax.Array, plan: ShardingPlan, *, causal=True
) -> jax.Array:
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, p, x, plan)
    positions = jnp.arange(S)
    q = nn.apply_rope(q, positions, cfg.rope_theta)
    k = nn.apply_rope(k, positions, cfg.rope_theta)
    out = xla_flash_attention(
        q, k, v, causal=causal, block_k=cfg.attn_block_k, plan=plan
    )
    out = plan.act(out, "heads")
    return nn.dense_apply({"w": p["wo"]}, out.reshape(B, S, -1))


def block_fwd(
    cfg: ModelConfig, plan: ShardingPlan, x: jax.Array, lp: Params
) -> jax.Array:
    # constrain the block OUTPUTS (still partial-summed over tp), not the
    # post-residual stream: GSPMD then lowers partial->seq-sharded as a
    # reduce-scatter (Megatron-SP) instead of all-reduce + re-slice
    att = _attn_train(cfg, lp["attn"], _norm(cfg, lp["attn_norm"], x), plan)
    x = x + plan.act(att, "hidden")
    mlp = _mlp(cfg, lp["mlp"], _norm(cfg, lp["mlp_norm"], x), plan)
    return plan.act(x + plan.act(mlp, "hidden"), "hidden")


def logits_fn(cfg: ModelConfig, params: Params, h: jax.Array, plan: ShardingPlan):
    h = _norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        w = params["embed"]["table"].astype(jnp.bfloat16).T
        logits = jnp.einsum("...d,dv->...v", h, w)
    else:
        logits = nn.dense_apply({"w": params["lm_head"]["w_lm"]}, h)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.logit_softcap
        )
    return mask_pad_logits(cfg, logits)


def mask_pad_logits(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    """Mask Megatron-style vocab-pad columns to -inf (elementwise, fuses)."""
    if cfg.padded_vocab == cfg.vocab:
        return logits
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(col < cfg.vocab, logits, jnp.asarray(-1e30, logits.dtype))


def embed_tokens(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    plan: ShardingPlan,
    patches: Optional[jax.Array] = None,
) -> jax.Array:
    h = nn.embedding_apply(params["embed"], tokens)
    if patches is not None:
        # llava-style stub frontend: project precomputed patch embeddings and
        # overwrite the first n_patches token slots with them.
        pe = nn.dense_apply(
            {"w": params["patch_proj"]["w_in"]}, patches.astype(jnp.bfloat16)
        )
        n = pe.shape[1]
        h = jnp.concatenate([pe, h[:, n:, :]], axis=1)
    return plan.act(h, "hidden")


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    plan: ShardingPlan,
    patches: Optional[jax.Array] = None,
) -> jax.Array:
    """Token ids (B, S) -> logits (B, S, V)."""
    h = embed_tokens(cfg, params, tokens, plan, patches)
    body = functools.partial(block_fwd, cfg, plan)
    h = nn.scan_layers(body, h, params["layers"], remat=cfg.remat)
    logits = logits_fn(cfg, params, h, plan)
    return plan.act(logits, "logits")


# ---------------------------------------------------------------------------
# KV-cache serving path
# ---------------------------------------------------------------------------


def cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)
    return {
        "k": jax.ShapeDtypeStruct(shape, jnp.bfloat16),
        "v": jax.ShapeDtypeStruct(shape, jnp.bfloat16),
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_spec(cfg, batch, max_len)
    )


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    plan: ShardingPlan,
    patches: Optional[jax.Array] = None,
):
    """Full-sequence forward that also returns the populated KV cache.

    Returns (last-position logits (B, V), cache).
    """
    B, S = tokens.shape
    h = embed_tokens(cfg, params, tokens, plan, patches)
    positions = jnp.arange(S)
    hd = cfg.resolved_head_dim

    def body(carry, lp):
        x = carry
        xn = _norm(cfg, lp["attn_norm"], x)
        q, k, v = _qkv(cfg, lp["attn"], xn, plan)
        q = nn.apply_rope(q, positions, cfg.rope_theta)
        kr = nn.apply_rope(k, positions, cfg.rope_theta)
        out = xla_flash_attention(
            q, kr, v, causal=True, block_k=cfg.attn_block_k, plan=plan
        )
        x = x + nn.dense_apply({"w": lp["attn"]["wo"]}, out.reshape(B, S, -1))
        x = x + _mlp(cfg, lp["mlp"], _norm(cfg, lp["mlp_norm"], x), plan)
        x = plan.act(x, "hidden")
        # store rope'd keys so decode never re-rotates the cache
        return x, (kr.astype(jnp.bfloat16), v.astype(jnp.bfloat16))

    def step(c, lp):
        c, kv = body(c, lp)
        return c, kv

    h, (ks, vs) = jax.lax.scan(step, h, params["layers"])
    cache = {"k": plan.act(ks, "cache"), "v": plan.act(vs, "cache")}
    last = logits_fn(cfg, params, h[:, -1:, :], plan)[:, 0, :]
    return plan.act(last, "last_logits"), cache


def decode_step(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,  # (B,) int32
    cache: Dict[str, jax.Array],
    pos,  # scalar int32: current length (tokens already in cache)
    plan: ShardingPlan,
):
    """One decode step against a (possibly sequence-sharded) KV cache."""
    B = token.shape[0]
    hd = cfg.resolved_head_dim
    h = nn.embedding_apply(params["embed"], token[:, None])
    h = plan.act(h, "decode_hidden")
    pos_arr = jnp.asarray(pos, jnp.int32)

    def body(carry, layer_in):
        x = carry
        lp, kc, vc = layer_in
        xn = _norm(cfg, lp["attn_norm"], x)
        q, k, v = _qkv(cfg, lp["attn"], xn, plan)
        q = nn.apply_rope(q, pos_arr[None], cfg.rope_theta)
        k = nn.apply_rope(k, pos_arr[None], cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos_arr, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos_arr, 1)
        out = decode_attention(q, kc, vc, kv_len=pos_arr + 1)
        out = plan.act(out, "decode_heads")
        x = x + nn.dense_apply({"w": lp["attn"]["wo"]}, out.reshape(B, 1, -1))
        x = x + _mlp(cfg, lp["mlp"], _norm(cfg, lp["mlp_norm"], x), plan)
        x = plan.act(x, "decode_hidden")
        return x, (kc, vc)

    h, (k_new, v_new) = jax.lax.scan(body, h, (params["layers"], cache["k"], cache["v"]))
    new_cache = {"k": plan.act(k_new, "cache"), "v": plan.act(v_new, "cache")}
    logits = logits_fn(cfg, params, h, plan)[:, 0, :]
    return plan.act(logits, "last_logits"), new_cache
