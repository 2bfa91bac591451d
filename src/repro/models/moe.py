"""Mixture-of-Experts transformer (deepseek-moe-16b, olmoe-1b-7b).

Expert dispatch is sort-based (megablocks-style): tokens are argsorted by
assigned expert, grouped into a static-capacity (E, C, d) tensor, pushed
through a batched expert GEMM with experts sharded over the ``model`` axis
(expert parallelism), and scatter-added back with their gate weights. This
avoids the O(T*E*C) one-hot dispatch of classic GShard, which is infeasible at
1M-token batches, while staying pure XLA for the 512-device dry-run.
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
from repro.models.model_api import Model, _input_specs, register_family
from repro.sharding.plan import ShardingPlan

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# router + dispatch
# ---------------------------------------------------------------------------


def router_probs(p: Params, x: jax.Array) -> jax.Array:
    """x: (T, d) -> (T, E) f32 softmax probabilities."""
    logits = jnp.einsum(
        "td,de->te", x.astype(jnp.float32), p["w_router"].astype(jnp.float32)
    )
    return jax.nn.softmax(logits, axis=-1), logits


def top_k_gates(probs: jax.Array, k: int, renormalize: bool = True):
    vals, idx = jax.lax.top_k(probs, k)  # (T, k)
    if renormalize:
        vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    return vals, idx


def sort_dispatch(
    x: jax.Array,  # (T, d)
    expert_idx: jax.Array,  # (T, k) int32
    gate_vals: jax.Array,  # (T, k) f32
    n_experts: int,
    capacity: int,
    expert_lo: jax.Array | int = 0,
    n_local: int | None = None,
):
    """Group tokens by expert into (E_local, C, d); returns grouped x + info.

    Tokens beyond an expert's capacity are dropped (capacity_factor-sized).
    ``expert_lo``/``n_local`` restrict dispatch to the local EP shard's
    expert range [expert_lo, expert_lo + n_local): assignments outside it
    are masked out, making the EP combine a pure psum over the model axis.
    vmap-safe (scatter-add instead of bincount).
    """
    if n_local is None:
        n_local = n_experts
    T, k = expert_idx.shape
    flat_e = expert_idx.reshape(-1)  # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)  # token id per assignment
    flat_g = gate_vals.reshape(-1)

    # stable sort by expert id
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]

    # position of each assignment within its expert's run:
    # pos[i] = i - start_offset[expert[i]]
    counts = jnp.zeros((n_experts,), jnp.int32).at[se].add(1, mode="drop")
    starts = jnp.cumsum(counts) - counts  # (E,)
    pos = jnp.arange(se.shape[0]) - starts[se]
    local_e = se - expert_lo
    keep = (pos < capacity) & (local_e >= 0) & (local_e < n_local)
    local_e = jnp.clip(local_e, 0, n_local - 1)

    slot = local_e * capacity + jnp.where(pos < capacity, pos, 0)  # (T*k,)
    # scatter token rows into the grouped buffer
    grouped = jnp.zeros((n_local * capacity, x.shape[1]), x.dtype)
    grouped = grouped.at[slot].add(
        jnp.where(keep[:, None], x[st], 0).astype(x.dtype), mode="drop"
    )
    grouped = grouped.reshape(n_local, capacity, x.shape[1])
    return grouped, (st, sg, slot, keep)


def sort_combine(
    expert_out: jax.Array,  # (E, C, d)
    scatter_info,
    T: int,
):
    st, sg, slot, keep = scatter_info
    rows = expert_out.reshape(-1, expert_out.shape[-1])[slot]  # (T*k, d)
    rows = rows * (sg * keep.astype(sg.dtype))[:, None].astype(rows.dtype)
    out = jnp.zeros((T, expert_out.shape[-1]), expert_out.dtype)
    return out.at[st].add(rows, mode="drop")


def load_balance_loss(probs: jax.Array, expert_idx: jax.Array, n_experts: int):
    """Switch-style aux loss: E * sum_e fraction_e * mean_prob_e."""
    T = probs.shape[0]
    assign = jnp.zeros((n_experts,), jnp.float32)
    assign = assign.at[expert_idx.reshape(-1)].add(1.0, mode="drop")
    frac = assign / jnp.maximum(jnp.sum(assign), 1.0)
    mean_p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(frac * mean_p)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def init_moe_ffn(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_expert, m.n_experts
    p = {
        "w_router": nn.fan_in_init(kg(), (d, E), jnp.float32),
        "e_gate": nn.fan_in_init(kg(), (E, d, f), jnp.bfloat16),
        "e_up": nn.fan_in_init(kg(), (E, d, f), jnp.bfloat16),
        "e_down": nn.fan_in_init(
            kg(), (E, f, d), jnp.bfloat16, scale=1.0 / (2 * cfg.n_layers) ** 0.5
        ),
    }
    if m.n_shared:
        fs = m.n_shared * m.d_expert
        p["shared"] = {
            "w_gate": nn.fan_in_init(kg(), (d, fs), jnp.bfloat16),
            "w_up": nn.fan_in_init(kg(), (d, fs), jnp.bfloat16),
            "w_down": nn.fan_in_init(
                kg(), (fs, d), jnp.bfloat16, scale=1.0 / (2 * cfg.n_layers) ** 0.5
            ),
        }
    return p


def _expert_mlp(p: Params, grouped: jax.Array) -> jax.Array:
    """(E, C, d) -> (E, C, d) batched swiglu expert GEMMs."""
    gate_h = jnp.einsum("ecd,edf->ecf", grouped, p["e_gate"].astype(grouped.dtype))
    up_h = jnp.einsum("ecd,edf->ecf", grouped, p["e_up"].astype(grouped.dtype))
    h = jax.nn.silu(gate_h.astype(jnp.float32)).astype(up_h.dtype) * up_h
    return jnp.einsum("ecf,efd->ecd", h, p["e_down"].astype(h.dtype))


def _local_moe(cfg, x, eidx, gates, e_params, capacity, expert_lo, n_local):
    """Per-example dispatch -> expert GEMM -> per-example combine.

    x: (B, S, d). Sorting happens inside each example (vmap over B), so no
    communication crosses examples; only the expert weights are EP-sharded.
    Returns the (partial, if n_local < E) MoE output (B, S, d).
    """
    m = cfg.moe
    B, S, d = x.shape

    def per_example(xe, ee, ge):
        return sort_dispatch(xe, ee, ge, m.n_experts, capacity, expert_lo, n_local)

    grouped, info = jax.vmap(per_example)(x, eidx, gates)  # (B, E_loc, C, d)
    out = jax.vmap(lambda g: _expert_mlp(e_params, g))(grouped)
    y = jax.vmap(lambda o, st, sg, sl, kp: sort_combine(o, (st, sg, sl, kp), S))(
        out, *info
    )
    return y


def moe_ffn(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,  # (B, S, d)
    plan: ShardingPlan,
    capacity_factor: Optional[float] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel MoE FFN, GSPMD-auto partitioned.

    Routing/top-k/sort/dispatch run *per example* (vmap over B), so every
    gather/scatter is local to the data shard. The grouped (B, E, C, d)
    tensor is then shard-constrained with experts over the ``model`` axis:
    GSPMD turns that reshard into the MoE all-to-all, the expert GEMMs
    contract locally against the (E/tp)-sharded expert weights, and the
    combine reshards back. Wire bytes per layer = 2 grouped-activation
    reshards — the TPU analogue of the NCCL all-to-all dispatch, with no
    manual collectives (a previous shard_map formulation replicated the
    global batch per device; see EXPERIMENTS.md §Perf).
    """
    m = cfg.moe
    if capacity_factor is None:
        capacity_factor = m.capacity_factor
    B, S, d = x.shape
    probs, logits = router_probs(p, x.reshape(B * S, d))
    gates, eidx = top_k_gates(probs, m.top_k)
    gates = gates.reshape(B, S, m.top_k)
    eidx = eidx.reshape(B, S, m.top_k)
    capacity = int(math.ceil(S * m.top_k / m.n_experts * capacity_factor))
    capacity = max(8, -(-capacity // 8) * 8)  # MXU-align the GEMM M-dim

    e_params = {k: p[k] for k in ("e_gate", "e_up", "e_down")}

    def per_example(xe, ee, ge):
        return sort_dispatch(xe, ee, ge, m.n_experts, capacity, 0, m.n_experts)

    grouped, info = jax.vmap(per_example)(x, eidx, gates)  # (B, E, C, d)
    grouped = plan.act(grouped, "grouped")  # experts -> model axis (EP)
    out = jax.vmap(lambda g: _expert_mlp(e_params, g))(grouped)
    out = plan.act(out, "grouped")
    y = jax.vmap(lambda o, st, sg, sl, kp: sort_combine(o, (st, sg, sl, kp), S))(
        out, *info
    )

    if m.n_shared:
        y = y + tfm._mlp(cfg, p["shared"], x, plan)

    aux = {
        "aux_loss": load_balance_loss(probs, eidx.reshape(-1, m.top_k), m.n_experts),
        "router_z": jnp.mean(
            jnp.square(jax.scipy.special.logsumexp(logits, axis=-1))
        ),
    }
    return y, aux


def init_block(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    return {
        "attn_norm": nn.rmsnorm_init(cfg.d_model),
        "attn": tfm.init_attn_layer(cfg, kg()),
        "mlp_norm": nn.rmsnorm_init(cfg.d_model),
        "moe": init_moe_ffn(cfg, kg()),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = nn.KeyGen(key)
    return {
        "embed": nn.embedding_init(kg(), cfg.padded_vocab, cfg.d_model),
        "layers": nn.stack_layer_init(
            functools.partial(init_block, cfg), kg(), cfg.n_layers
        ),
        "final_norm": nn.rmsnorm_init(cfg.d_model),
        "lm_head": {"w_lm": nn.fan_in_init(kg(), (cfg.d_model, cfg.padded_vocab), jnp.bfloat16)},
    }


def block_fwd(cfg: ModelConfig, plan: ShardingPlan, carry, lp: Params):
    x, aux_acc = carry
    x = x + tfm._attn_train(cfg, lp["attn"], tfm._norm(cfg, lp["attn_norm"], x), plan)
    x = plan.act(x, "hidden")
    y, aux = moe_ffn(cfg, lp["moe"], tfm._norm(cfg, lp["mlp_norm"], x), plan)
    x = plan.act(x + y, "hidden")
    aux_acc = {
        "aux_loss": aux_acc["aux_loss"] + aux["aux_loss"],
        "router_z": aux_acc["router_z"] + aux["router_z"],
    }
    return x, aux_acc


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, plan: ShardingPlan):
    h = tfm.embed_tokens(cfg, params, tokens, plan)
    aux0 = {"aux_loss": jnp.float32(0), "router_z": jnp.float32(0)}
    body = functools.partial(block_fwd, cfg, plan)
    h, aux = nn.scan_layers(body, (h, aux0), params["layers"], remat=cfg.remat)
    logits = tfm.logits_fn(cfg, params, h, plan)
    return plan.act(logits, "logits"), aux


# ---------------------------------------------------------------------------
# serving path (KV cache identical to dense; MoE FFN applied per step)
# ---------------------------------------------------------------------------


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, plan: ShardingPlan):
    B, S = tokens.shape
    h = tfm.embed_tokens(cfg, params, tokens, plan)
    positions = jnp.arange(S)

    def body(x, lp):
        xn = tfm._norm(cfg, lp["attn_norm"], x)
        q, k, v = tfm._qkv(cfg, lp["attn"], xn, plan)
        q = nn.apply_rope(q, positions, cfg.rope_theta)
        kr = nn.apply_rope(k, positions, cfg.rope_theta)
        out = tfm.xla_flash_attention(
            q, kr, v, causal=True, block_k=cfg.attn_block_k, plan=plan
        )
        x = x + nn.dense_apply({"w": lp["attn"]["wo"]}, out.reshape(B, S, -1))
        y, _ = moe_ffn(cfg, lp["moe"], tfm._norm(cfg, lp["mlp_norm"], x), plan)
        x = plan.act(x + y, "hidden")
        return x, (kr.astype(jnp.bfloat16), v.astype(jnp.bfloat16))

    h, (ks, vs) = jax.lax.scan(body, h, params["layers"])
    cache = {"k": plan.act(ks, "cache"), "v": plan.act(vs, "cache")}
    last = tfm.logits_fn(cfg, params, h[:, -1:, :], plan)[:, 0, :]
    return plan.act(last, "last_logits"), cache


def decode_step(cfg, params, token, cache, pos, plan: ShardingPlan):
    B = token.shape[0]
    h = nn.embedding_apply(params["embed"], token[:, None])
    h = plan.act(h, "decode_hidden")
    pos_arr = jnp.asarray(pos, jnp.int32)

    def body(x, layer_in):
        lp, kc, vc = layer_in
        xn = tfm._norm(cfg, lp["attn_norm"], x)
        q, k, v = tfm._qkv(cfg, lp["attn"], xn, plan)
        q = nn.apply_rope(q, pos_arr[None], cfg.rope_theta)
        k = nn.apply_rope(k, pos_arr[None], cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos_arr, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos_arr, 1)
        from repro.models.attention import decode_attention

        out = decode_attention(q, kc, vc, kv_len=pos_arr + 1)
        x = x + nn.dense_apply({"w": lp["attn"]["wo"]}, out.reshape(B, 1, -1))
        y, _ = moe_ffn(cfg, lp["moe"], tfm._norm(cfg, lp["mlp_norm"], x), plan)
        x = plan.act(x + y, "decode_hidden")
        return x, (kc, vc)

    h, (k_new, v_new) = jax.lax.scan(
        body, h, (params["layers"], cache["k"], cache["v"])
    )
    logits = tfm.logits_fn(cfg, params, h, plan)[:, 0, :]
    return plan.act(logits, "last_logits"), {
        "k": plan.act(k_new, "cache"),
        "v": plan.act(v_new, "cache"),
    }


@register_family("moe")
def _build_moe(cfg: ModelConfig) -> Model:
    def init(key):
        return init_params(cfg, key)

    def loss(params, batch, plan: ShardingPlan):
        logits, aux = forward(cfg, params, batch["tokens"], plan)
        base, metrics = losses.softmax_cross_entropy(logits, batch["labels"])
        m = cfg.moe
        total = (
            base
            + m.router_aux_coef * aux["aux_loss"] / cfg.n_layers
            + m.router_z_coef * aux["router_z"] / cfg.n_layers
        )
        metrics = dict(metrics, aux_loss=aux["aux_loss"] / cfg.n_layers)
        return total, metrics

    return Model(
        cfg=cfg,
        init=init,
        loss=loss,
        prefill=lambda params, batch, plan: prefill(cfg, params, batch["tokens"], plan),
        decode=lambda params, batch, cache, pos, plan: decode_step(
            cfg, params, batch["token"], cache, pos, plan
        ),
        cache_spec=lambda b, s: tfm.cache_spec(cfg, b, s),
        input_specs=lambda suite: _input_specs(cfg, suite),
    )
