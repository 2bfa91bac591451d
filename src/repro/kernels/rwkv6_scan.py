"""Chunked WKV6 (RWKV-6 'Finch') linear-attention scan — Pallas TPU kernel.

The recurrence (per head, state S in R^{KxV}, data-dependent decay w_t):

    o_t = r_t @ (S_{t-1} + (u * k_t) v_t^T)
    S_t = diag(exp(logw_t)) S_{t-1} + k_t v_t^T

is the attention-free hot spot of the assigned pool. A token-by-token scan
is latency-bound (T sequential steps of rank-1 updates); the kernel instead
uses the chunked form: inside a chunk of C tokens the recurrence expands to
a bounded pairwise sum (every exponent is a *difference of cumulative
log-decays*, hence <= 0 — overflow-safe in f32, unlike the factored
(r e^{+cum}) @ (k e^{-cum})^T form which overflows once |cum| > 88), and
chunk-to-chunk state is carried in VMEM.

TPU mapping:
  * grid (B, H, n_chunks), chunk dim innermost: the (K, V) f32 state lives
    in VMEM scratch across the whole chunk sweep — zero HBM state traffic;
  * intra-chunk work is two MXU matmuls ((C,K)x(K,V) cross-chunk term,
    (C,C)x(C,V) pairwise term) plus VPU elementwise decay algebra;
  * the (C, C, K) pairwise-decay tensor is the VMEM budget knob:
    C=64, K=64 -> 1 MiB f32, leaving room for double-buffered r/k/v/w tiles.

Validated against ``ref.wkv6_reference`` in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(
    r_ref,  # (1, 1, C, K)
    k_ref,  # (1, 1, C, K)
    v_ref,  # (1, 1, C, V)
    w_ref,  # (1, 1, C, K) log-decay <= 0
    u_ref,  # (1, 1, K)
    s0_ref,  # (1, 1, K, V) initial state
    o_ref,  # (1, 1, C, V)
    sT_ref,  # (1, 1, K, V) final state
    S,  # VMEM (K, V) f32 carried state
    *,
    chunk: int,
):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        S[...] = s0_ref[0, 0].astype(jnp.float32)

    C = chunk
    r = r_ref[0, 0].astype(jnp.float32)  # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)  # (C, V)
    w = w_ref[0, 0].astype(jnp.float32)  # (C, K), <= 0
    u = u_ref[0, 0].astype(jnp.float32)  # (K,)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # inclusive cumulative log-decay as a lower-triangular matmul: Mosaic
    # has no cumsum lowering
    clw = jax.lax.dot_general(
        (t_idx >= s_idx).astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    clw_ex = clw - w  # exclusive

    # pairwise decay for s < t: exp(clw_ex[t] - clw[s]) (<= 0 exponent)
    diff = clw_ex[:, None, :] - clw[None, :, :]  # (C, C, K)
    # the mask is built at rank 3: Mosaic cannot append a lane dim to (C, C)
    t3 = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    s3 = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    decay = jnp.exp(jnp.where(t3 > s3, diff, -jnp.inf))  # (C,C,K)

    # scores[t,s] = sum_k r[t,k] k[s,k] decay[t,s,k]
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1)  # (C,C)
    out = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, V)
    # diagonal bonus: (r_t . (u * k_t)) v_t
    out += jnp.sum(r * k * u[None, :], axis=-1, keepdims=True) * v
    # cross-chunk: r decayed to chunk start @ carried state
    out += jax.lax.dot_general(
        r * jnp.exp(clw_ex), S[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0] = out.astype(o_ref.dtype)

    # state update: S' = exp(clw[-1]) * S + sum_s (k_s e^{clw[-1]-clw[s]}) v_s^T
    last = clw[-1:, :]  # (1, K)
    kdec = k * jnp.exp(last - clw)  # (C, K)
    S[...] = jnp.exp(last[0])[:, None] * S[...] + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ic == nc - 1)
    def _finalize():
        sT_ref[0, 0] = S[...]


def wkv6_scan(
    r: jax.Array,  # (B, T, H, K)
    k: jax.Array,  # (B, T, H, K)
    v: jax.Array,  # (B, T, H, V)
    logw: jax.Array,  # (B, T, H, K) log-decay <= 0
    u: jax.Array,  # (H, K) bonus
    state0: jax.Array,  # (B, H, K, V)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B,T,H,V) f32, final state (B,H,K,V) f32)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, T)
    if T % chunk:
        raise ValueError(f"T={T} must be divisible by chunk={chunk}")
    nc = T // chunk

    # head-major (B, H, T, K): a (chunk, K) block per head fits the TPU's
    # (8, 128) tiling, where a one-head slab of (B, T, H, K) would not
    r, k, v, logw = (x.transpose(0, 2, 1, 3) for x in (r, k, v, logw))
    grid = (B, H, nc)
    seq_spec_k = pl.BlockSpec((1, 1, chunk, K), lambda b, h, ic: (b, h, ic, 0))
    seq_spec_v = pl.BlockSpec((1, 1, chunk, V), lambda b, h, ic: (b, h, ic, 0))
    state_spec = pl.BlockSpec((1, 1, K, V), lambda b, h, ic: (b, h, 0, 0))

    out, state = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            seq_spec_k,
            seq_spec_k,
            seq_spec_v,
            seq_spec_k,
            pl.BlockSpec((1, 1, K), lambda b, h, ic: (h, 0, 0)),
            state_spec,
        ],
        out_specs=[seq_spec_v, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
        name="rwkv6_scan",
    )(r, k, v, logw, u.reshape(H, 1, K), state0)
    return out.transpose(0, 2, 1, 3), state
