"""jit'd public wrappers around the Pallas kernels.

Layout adaptation (model API uses (B, S, H, D); kernels use the GQA-folded
(B, KVH, S, G, D)), custom_vjp wiring for training, and the execution-mode
switch:

  * ``mode='tpu'``       — compiled Pallas (the deployment path)
  * ``mode='interpret'`` — Pallas interpret=True (CPU correctness runs;
                           this is what the test suite sweeps)
  * ``mode='ref'``       — the pure-jnp oracle (debugging / oracles)
  * ``mode=None``        — auto: TPU backend -> 'tpu', else 'ref' (XLA path
                           stays the CPU-dry-run default so 512-device
                           lowering never pays interpret-mode cost)
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import decode_attention as da
from repro.kernels import rwkv6_scan as rk
from repro.kernels import ref


def _auto_mode(mode: Optional[str]) -> str:
    if mode is not None:
        return mode
    return "tpu" if jax.default_backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
# flash attention (training: fwd + bwd kernels under custom_vjp)
# ---------------------------------------------------------------------------


def _fold(q, kvh):
    """(B, S, H, D) -> (B, KVH, S, G, D)."""
    B, S, H, D = q.shape
    return q.reshape(B, S, kvh, H // kvh, D).transpose(0, 2, 1, 3, 4)


def _unfold(qf):
    """(B, KVH, S, G, D) -> (B, S, H, D)."""
    B, KVH, S, G, D = qf.shape
    return qf.transpose(0, 2, 1, 3, 4).reshape(B, S, KVH * G, D)


def _kv_fold(k):
    """(B, S, KVH, D) -> (B, KVH, S, D)."""
    return k.transpose(0, 2, 1, 3)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    o, _ = fa.flash_attention_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = fa.flash_attention_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = fa.flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, KVH, D)
    v: jax.Array,  # (B, Skv, KVH, D)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = fa.BLOCK,
    block_k: int = fa.BLOCK,
    mode: Optional[str] = None,
) -> jax.Array:
    """GQA flash attention with the model-API layout. Differentiable."""
    mode = _auto_mode(mode)
    if mode == "ref":
        return ref.mha_reference(q, k, v, causal=causal, scale=scale)
    D = q.shape[-1]
    scale = D**-0.5 if scale is None else scale
    KVH = k.shape[2]
    qf = _fold(q, KVH)
    o = _flash(
        qf, _kv_fold(k), _kv_fold(v), causal, scale,
        block_q, block_k, mode == "interpret",
    )
    return _unfold(o)


def decode_attention(
    q: jax.Array,  # (B, 1, H, D) or (B, H, D)
    k_cache: jax.Array,  # (B, Smax, KVH, D)
    v_cache: jax.Array,  # (B, Smax, KVH, D)
    *,
    kv_len,
    scale: Optional[float] = None,
    block_k: int = 1024,
    mode: Optional[str] = None,
) -> jax.Array:
    """Single-token decode attention; returns q-shaped output."""
    mode = _auto_mode(mode)
    squeeze = q.ndim == 4
    q3 = q[:, 0] if squeeze else q
    if mode == "ref":
        out = ref.decode_attention_reference(
            q3, k_cache, v_cache, kv_len=kv_len, scale=scale
        )
    else:
        out = da.decode_attention(
            q3, k_cache, v_cache, jnp.asarray(kv_len, jnp.int32),
            scale=scale, block_k=block_k, interpret=mode == "interpret",
        )
    return out[:, None] if squeeze else out


def wkv6(
    r, k, v, logw, u, state0,
    *,
    chunk: int = 64,
    mode: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked WKV6 scan; returns (out (B,T,H,V) f32, state (B,H,K,V) f32)."""
    mode = _auto_mode(mode)
    if mode == "ref":
        return ref.wkv6_reference(r, k, v, logw, u, state0)
    return rk.wkv6_scan(
        r, k, v, logw, u, state0, chunk=chunk, interpret=mode == "interpret"
    )
