"""Attention implementations.

``xla_flash`` is the default lowering path: a blocked online-softmax attention
expressed with ``lax.scan`` over KV blocks, so the S x S score matrix is never
materialized (required for the 32k prefill cells) while remaining pure XLA —
this is what the 512-device dry-run compiles. The Pallas kernel in
``repro.kernels.flash_attention`` is the TPU hot-path with identical math and
is validated against ``repro.kernels.ref`` oracles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B,Sq,KVH,G,D), k: (B,bk,KVH,D) -> (B,Sq,KVH,G,bk), f32."""
    return jnp.einsum(
        "bqhgd,bkhd->bqhgk", q, k, preferred_element_type=jnp.float32
    )


def _gqa_values(p, v):
    """p: (B,Sq,KVH,G,bk) f32, v: (B,bk,KVH,D) -> (B,Sq,KVH,G,D) f32."""
    return jnp.einsum(
        "bqhgk,bkhd->bqhgd", p, v, preferred_element_type=jnp.float32
    )


def _kernel_mode() -> Optional[str]:
    """Pallas execution mode for the training path: compiled on a TPU backend,
    none (the XLA path) elsewhere."""
    return "tpu" if jax.default_backend() == "tpu" else None


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_k: int = 1024,
    q_offset: int = 0,
    scale: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,
    plan=None,
) -> jax.Array:
    """Backend dispatch: Pallas kernel on TPU, XLA scan path elsewhere.

    The XLA path is what the 512-placeholder-device dry-run lowers (identical
    math, no Mosaic dependency); on a real TPU the Pallas kernel from
    ``repro.kernels`` takes over. kv_len/q_offset users (decode) stay XLA.
    Under a mesh (``plan.mesh``) the kernel runs per shard inside
    ``shard_map``: XLA cannot partition a Mosaic custom call itself.
    """
    mode = _kernel_mode()
    if (
        mode is not None
        and kv_len is None
        and q_offset == 0
        and q.shape[1] % 512 == 0
        and k.shape[1] % 512 == 0
    ):
        from repro.kernels import ops

        def kernel(q, k, v):
            return ops.flash_attention(q, k, v, causal=causal, scale=scale, mode=mode)

        if plan is None or plan.mesh is None:
            return kernel(q, k, v)
        q_spec, kv_spec = plan.spec("heads"), plan.spec("kv_heads")
        if q_spec[2] != kv_spec[2]:
            # heads split over the model axis only when q and kv heads both
            # divide it, so each shard keeps whole GQA groups
            q_spec = P(q_spec[0], None, None, None)
            kv_spec = P(kv_spec[0], None, None, None)
        return jax.shard_map(
            kernel, mesh=plan.mesh, in_specs=(q_spec, kv_spec, kv_spec),
            out_specs=q_spec, check_vma=False,
        )(q, k, v)
    return xla_flash_attention(
        q, k, v, causal=causal, block_k=block_k, q_offset=q_offset,
        scale=scale, kv_len=kv_len,
    )


def xla_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_k: int = 1024,
    q_offset: int = 0,
    scale: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Blocked GQA attention with online softmax, pure XLA.

    q: (B, Sq, H, D); k, v: (B, Skv, KVH, D). H = KVH * G.
    ``q_offset``: absolute position of q[0] (prefill=0; decode=cache length).
    ``kv_len``: optional dynamic valid-KV length (decode with ring cache).
    Returns (B, Sq, H, D) in q.dtype.
    """
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    scale = scale if scale is not None else D**-0.5

    # q is upcast once (small); K/V blocks stay in storage dtype and the
    # score/value dots accumulate in f32 — avoids materializing f32 copies
    # of the whole K/V tensors (2x HBM traffic at 32k prefill)
    qf = (q.reshape(B, Sq, KVH, G, D).astype(jnp.float32) * scale).astype(k.dtype)
    block_k = min(block_k, Skv)
    n_blocks = -(-Skv // block_k)
    pad = n_blocks * block_k - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, n_blocks, block_k, KVH, D).swapaxes(0, 1)
    vb = v.reshape(B, n_blocks, block_k, KVH, D).swapaxes(0, 1)

    q_pos = q_offset + jnp.arange(Sq)  # (Sq,)

    def body(carry, inputs):
        acc, m, l = carry
        idx, kblk, vblk = inputs
        kv_pos = idx * block_k + jnp.arange(block_k)  # (bk,)
        s = _gqa_scores(qf, kblk)  # (B,Sq,KVH,G,bk)
        mask = jnp.ones((Sq, block_k), jnp.bool_)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        mask &= (kv_pos < Skv)[None, :] if pad else True
        if kv_len is not None:
            mask &= (kv_pos[None, :] < kv_len)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + _gqa_values(p, vblk)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Sq, KVH, G, D), jnp.float32)
    m0 = jnp.full((B, Sq, KVH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KVH, G), jnp.float32)
    idxs = jnp.arange(n_blocks)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (idxs, kb, vb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    *,
    kv_len: jax.Array | int,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-step decode attention.

    q: (B, 1, H, D); caches: (B, Smax, KVH, D). ``kv_len``: number of valid
    cache entries (scalar). The cache sequence dim may be sharded (SP decode);
    the masked softmax reduces across it with f32 stats.
    """
    B, _, H, D = q.shape
    _, Smax, KVH, _ = k_cache.shape
    G = H // KVH
    scale = scale if scale is not None else D**-0.5
    # keep the cache in its storage dtype (bf16): upcasting it would
    # materialize an f32 copy of the whole KV shard (2x HBM reads + huge
    # temps at 32k-500k contexts); the dots accumulate in f32 instead.
    qf = (q.reshape(B, KVH, G, D).astype(jnp.float32) * scale).astype(k_cache.dtype)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qf, k_cache,
        preferred_element_type=jnp.float32,
    )  # (B,KVH,G,Smax) f32
    pos = jnp.arange(Smax)
    s = jnp.where(pos[None, None, None, :] < kv_len, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, D).astype(q.dtype)
