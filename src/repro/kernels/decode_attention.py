"""Single-query decode attention over a long KV cache — Pallas TPU kernel.

The decode_32k / long_500k serving cells attend one new token against a
seq_len-deep cache: the op is *memory-bound* (arithmetic intensity
≈ 2 FLOPs/byte « the 240 FLOP/byte ridge), so the kernel is shaped around
HBM→VMEM streaming, not MXU occupancy:

  * grid (B, nk) with the KV dim innermost: each sequence streams its KV
    stripe block-by-block through VMEM exactly once while the (KVH, G, D)
    query tile and the f32 accumulator stay resident. A block holds every KV
    head, so the cache is read in its stored (B, S, KVH, D) layout (a
    one-head block would be a (1, D) slab, which the TPU's (8, 128) tiling
    refuses);
  * ``block_k`` is sized so two KV blocks (k + v, bf16) fit VMEM alongside
    the accumulator, letting the implicit Pallas double-buffering overlap
    the next block's DMA with the current block's compute;
  * the dynamic valid length (``kv_len``, a traced scalar) rides in SMEM as
    a scalar-prefetch operand and masks the tail block.

Validated against ``ref.decode_attention_reference`` in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _decode_kernel(
    kv_len_ref,  # SMEM (1,) int32 — scalar prefetch
    q_ref,  # (1, KVH, G, D)
    k_ref,  # (1, block_k, KVH, D)
    v_ref,  # (1, block_k, KVH, D)
    o_ref,  # (1, KVH, G, D)
    acc,  # VMEM (KVH, G, D) f32
    m,  # VMEM (KVH, G, LANES) f32
    l,  # VMEM (KVH, G, LANES) f32
    *,
    scale: float,
    block_k: int,
):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)
    kv_len = kv_len_ref[0]

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    # skip blocks entirely beyond the valid cache length
    @pl.when(ik * block_k < kv_len)
    def _compute():
        KVH, G = q_ref.shape[1], q_ref.shape[2]
        kv_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_k), 1
        )
        # the block carries every KV head of the stripe, so the cache is read
        # in its stored (B, S, KVH, D) layout; heads are a static loop
        for h in range(KVH):
            q = q_ref[0, h].astype(jnp.float32) * scale  # (G, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)  # (block_k, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # (G, block_k)
            s = jnp.where(kv_pos < kv_len, s, NEG_INF)

            m_prev = m[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l[h] = jnp.broadcast_to(
                l[h][:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True), (G, LANES)
            )
            acc[h] = acc[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m[h] = jnp.broadcast_to(m_new, (G, LANES))

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc[...] / jnp.maximum(l[:, :, :1], 1e-30)).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # (B, H, D) one new token per sequence
    k_cache: jax.Array,  # (B, Smax, KVH, D)
    v_cache: jax.Array,  # (B, Smax, KVH, D)
    kv_len: jax.Array,  # scalar int32 — valid cache entries
    *,
    scale: Optional[float] = None,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, H, D) attention output in q.dtype."""
    B, H, D = q.shape
    _, Smax, KVH, _ = k_cache.shape
    G = H // KVH
    scale = D**-0.5 if scale is None else scale
    block_k = min(block_k, Smax)
    if Smax % block_k:
        raise ValueError(f"Smax={Smax} must divide block_k={block_k}")
    nk = Smax // block_k

    qr = q.reshape(B, KVH, G, D)
    kv_len_arr = jnp.asarray(kv_len, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, KVH, G, D), lambda b, ik, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_k, KVH, D), lambda b, ik, *_: (b, ik, 0, 0)),
            pl.BlockSpec((1, block_k, KVH, D), lambda b, ik, *_: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KVH, G, D), lambda b, ik, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVH, G, D), jnp.float32),
            pltpu.VMEM((KVH, G, LANES), jnp.float32),
            pltpu.VMEM((KVH, G, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(kv_len_arr, qr, k_cache, v_cache)
    return out.reshape(B, H, D)
