"""GPipe-style pipeline parallelism over ``shard_map`` + ``ppermute``.

The paper's technique partitions *between* jobs, so PP is not the default
axis mapping — but a 1000+-node posture needs it available. This module
implements a self-contained microbatch pipeline for the stacked-layer dense
transformer: stage s owns layers [s*L/S, (s+1)*L/S); activations flow stage
to stage with ``collective_permute``; the classic GPipe schedule runs
(num_micro + num_stages - 1) ticks with bubble fraction (S-1)/(M+S-1).

Used by tests (8 host devices) and by the hillclimb as an alternative
mapping; correctness oracle = the plain scanned forward.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import module as nn
from repro.models import transformer as tfm
from repro.sharding.plan import ShardingPlan


def pipeline_forward(
    cfg: ModelConfig,
    params,
    tokens: jax.Array,  # (M, mb, S) microbatched token ids
    mesh: Mesh,
    *,
    stage_axis: str = "stage",
):
    """Pipelined forward producing logits (M, mb, S, V).

    ``params['layers']`` leaves have leading dim L = n_layers; the stage axis
    must divide L. Embedding/head run on every stage (cheap, replicated math)
    with masking selecting the true first/last stage contributions.
    """
    n_stages = mesh.shape[stage_axis]
    L = cfg.n_layers
    assert L % n_stages == 0, (L, n_stages)
    per_stage = L // n_stages
    M = tokens.shape[0]
    plan = ShardingPlan(None, {}, (), None)  # inside shard_map: no constraints

    def stage_fn(layers_stacked, embed, final_norm, lm_head, toks):
        """Runs on one device = one stage. toks: (M, mb, S)."""
        sid = jax.lax.axis_index(stage_axis)
        mb, S = toks.shape[1], toks.shape[2]
        d = cfg.d_model

        h_in = nn.embedding_apply(embed, toks)  # (M, mb, S, d) — used by stage 0

        def tick(carry, t):
            buf = carry  # (mb, S, d) activation arriving this tick
            # microbatch index this stage works on at tick t
            m_idx = t - sid
            active = (m_idx >= 0) & (m_idx < M)
            x = jnp.where(
                sid == 0,
                h_in[jnp.clip(m_idx, 0, M - 1)].astype(jnp.float32),
                buf.astype(jnp.float32),
            ).astype(jnp.bfloat16)

            body = functools.partial(tfm.block_fwd, cfg, plan)
            y = nn.scan_layers(body, x, layers_stacked)
            y = jnp.where(active, y.astype(jnp.float32), 0.0)

            # pass activation to the next stage (ring; last stage's output
            # wraps to stage 0 where it is ignored)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(y, stage_axis, perm)
            # last stage emits logits for microbatch m_idx
            out = jnp.where(
                active & (sid == n_stages - 1),
                y.astype(jnp.float32),
                0.0,
            )
            return nxt.astype(jnp.bfloat16), (out, m_idx, active & (sid == n_stages - 1))

        ticks = M + n_stages - 1
        buf0 = jnp.zeros((mb, S, d), jnp.bfloat16)
        _, (outs, m_idxs, valid) = jax.lax.scan(
            tick, buf0, jnp.arange(ticks)
        )
        # scatter tick outputs back to microbatch order
        h_out = jnp.zeros((M, mb, S, d), jnp.float32)
        h_out = h_out.at[jnp.clip(m_idxs, 0, M - 1)].add(
            outs * valid[:, None, None, None]
        )
        h_out = h_out.astype(jnp.bfloat16)
        logits = tfm.logits_fn(cfg, {**lm_head, "final_norm": final_norm}, h_out, plan)
        # only the last stage holds real logits; share them with everyone
        logits = jax.lax.psum(
            jnp.where(sid == n_stages - 1, logits.astype(jnp.float32), 0.0),
            stage_axis,
        )
        return logits

    # split stacked layers across stages; replicate everything else
    lspec = jax.tree_util.tree_map(
        lambda a: P(*((stage_axis,) + (None,) * (a.ndim - 1))), params["layers"]
    )
    rep = lambda tree: jax.tree_util.tree_map(lambda a: P(), tree)
    head = {k: params[k] for k in ("lm_head",) if k in params}
    if cfg.tie_embeddings:
        head = {"embed": params["embed"]}

    fn = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(
            lspec,
            rep(params["embed"]),
            rep(params["final_norm"]),
            rep(head),
            P(),
        ),
        out_specs=P(),
        # the tick scan mixes stage-varying (buf) and replicated (h_in)
        # carries; vma checking would demand explicit pvary casts that XLA
        # elides anyway (and whose copy-combiner all-reduces crash XLA:CPU —
        # see models/moe.py)
        check_vma=False,
    )
    return fn(params["layers"], params["embed"], params["final_norm"], head, tokens)
