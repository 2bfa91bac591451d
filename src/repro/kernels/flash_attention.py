"""Blocked causal GQA flash attention — Pallas TPU kernels (fwd + bwd).

TPU adaptation notes (DESIGN.md §2): the CUDA flash-attention algorithm keys
on warp-level tiling and shared-memory banking; on TPU the same online-
softmax recurrence is re-tiled for the MXU and VMEM:

  * the G query heads sharing one KV head are FOLDED into the row dim of the
    q tile, so the score matmul is a single (Bq*G, D) x (D, Bk) MXU op —
    GQA comes for free instead of a per-head loop;
  * the grid is (B, KVH, nq, nk) with the KV dim innermost: TPU grid
    execution is sequential over the last axis, so the f32 accumulator and
    the online-softmax stats (m, l) live in VMEM scratch across the KV
    sweep of each q tile — the HBM traffic is exactly one read of q/k/v and
    one write of o per tile;
  * softmax stats are kept as (rows, 128) lane-replicated tiles (VREG-
    friendly broadcast instead of (rows, 1) relayouts); the saved lse is
    lane-replicated in HBM too, because Mosaic cannot turn a lane-dense
    (Bq, G) tile back into the (rows, 1) column the backward needs;
  * causal q-tiles skip fully-masked KV tiles via ``pl.when`` on the grid
    index (≈2x fewer MXU ops at long seq), and only the tiles that straddle
    the diagonal build the mask: a tile wholly below it runs a second body
    with no iota, compare or select (``tile_classes`` counts each kind).

Backward follows the two-kernel FlashAttention-2 schedule: a dk/dv kernel
with the q dim innermost, and a dq kernel with the KV dim innermost; both
recompute p from (q, k, lse) so no S x S tensor ever exists, and both
recompute the row term delta = sum(o * do) from their (o, do) tiles, which
yields it directly as a (rows, 1) column.

Validated against ``ref.mha_reference`` in interpret mode (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # stat tiles are lane-replicated to this width
BLOCK = 512  # default q and KV block of every entry point
# At block 512 with G=4 folded rows the backward kernels hold several
# (2048, 512) f32 tiles at once, just over Mosaic's 16 MiB default scoped
# VMEM; v5e has 128 MiB of VMEM per core.
_BWD_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2**20)


def _row_positions(block_q: int, g: int, iq, q_offset: int):
    """Absolute q position of each folded (q, g) row: row -> q index."""
    rows = block_q * g
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return q_offset + iq * block_q + r // g  # (rows, 1)


def _causal_mask(s, *, block_q, block_k, g, iq, ik, q_offset):
    """Scores of one tile with every key after its row's query set to NEG_INF."""
    kv_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(_row_positions(block_q, g, iq, q_offset) >= kv_pos, s, NEG_INF)


def _blocks(sq: int, skv: int, block_q: int, block_k: int) -> Tuple[int, int]:
    """Blocks as the kernels use them: clamped to the lengths, which they must
    divide (no tile holds padding, so no tile masks beyond the diagonal)."""
    block_q, block_k = min(block_q, sq), min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(f"seq ({sq},{skv}) must divide blocks ({block_q},{block_k})")
    return block_q, block_k


# Where a (q tile iq, KV tile ik) pair lies against the causal diagonal. Both
# take grid indices, traced in a kernel or Python ints in ``tile_classes``.
def _tile_live(iq, ik, *, causal, block_q, block_k, q_offset):
    """Some query of the tile may see some key of it: the tile's first KV
    position is at or before its last query position."""
    return not causal or ik * block_k <= q_offset + (iq + 1) * block_q - 1


def _tile_masked(iq, ik, *, causal, block_q, block_k, q_offset):
    """The tile straddles the diagonal (or lies above it): its last KV
    position passes its first query position. A live tile that is not
    masked lies wholly below the diagonal, and its mask is all true."""
    return causal and (ik + 1) * block_k - 1 > q_offset + iq * block_q


def _geom(kw: dict) -> dict:
    """The keywords that place a tile against the diagonal."""
    return {k: kw[k] for k in ("causal", "block_q", "block_k", "q_offset")}


def _run_live_tile(tile, iq, ik, **geom):
    """Call ``tile(masked)`` on a live tile: ``masked=True`` only where it
    straddles the diagonal. Tiles above the diagonal run nothing."""
    if not geom["causal"]:
        tile(False)
        return
    live = _tile_live(iq, ik, **geom)
    masked = _tile_masked(iq, ik, **geom)
    pl.when(live & masked)(lambda: tile(True))
    pl.when(live & ~masked)(lambda: tile(False))


def tile_classes(sq: int, skv: int, block_q: int, block_k: int, causal: bool,
                 q_offset: int = 0) -> dict:
    """Tiles of one (batch, KV head) grid by how the kernels treat them:
    ``skipped`` (above the diagonal), ``masked`` (straddling it) and
    ``unmasked`` (live, mask all true). The blocks are clamped as the kernels
    clamp them."""
    block_q, block_k = _blocks(sq, skv, block_q, block_k)
    geom = dict(causal=causal, block_q=block_q, block_k=block_k, q_offset=q_offset)
    counts = {"skipped": 0, "masked": 0, "unmasked": 0}
    for iq in range(sq // block_q):
        for ik in range(skv // block_k):
            if not _tile_live(iq, ik, **geom):
                counts["skipped"] += 1
            elif _tile_masked(iq, ik, **geom):
                counts["masked"] += 1
            else:
                counts["unmasked"] += 1
    return counts


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # (1, 1, Bq, G, D)
    k_ref,  # (1, 1, Bk, D)
    v_ref,  # (1, 1, Bk, D)
    o_ref,  # (1, 1, Bq, G, D)
    lse_ref,  # (1, 1, Bq*G, LANES) lane-replicated
    acc,  # VMEM (Bq*G, D) f32
    m,  # VMEM (Bq*G, LANES) f32
    l,  # VMEM (Bq*G, LANES) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    g: int,
    q_offset: int,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    rows = block_q * g

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    def _compute(masked: bool):
        q = q_ref[0, 0].reshape(rows, q_ref.shape[-1]).astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)  # (Bk, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (Bk, D)
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (rows, Bk)
        if masked:
            s = _causal_mask(s, block_q=block_q, block_k=block_k, g=g, iq=iq,
                             ik=ik, q_offset=q_offset)

        m_prev = m[:, :1]  # (rows, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (rows, Bk)
        corr = jnp.exp(m_prev - m_new)  # (rows, 1)
        l_new = l[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m[...] = jnp.broadcast_to(m_new, m.shape)
        l[...] = jnp.broadcast_to(l_new, l.shape)

    _run_live_tile(_compute, iq, ik, causal=causal, block_q=block_q,
                   block_k=block_k, q_offset=q_offset)

    @pl.when(ik == nk - 1)
    def _finalize():
        lsum = l[:, :1]
        out = acc[...] / jnp.maximum(lsum, 1e-30)
        o_ref[0, 0] = out.reshape(o_ref.shape[2:]).astype(o_ref.dtype)
        lse_ref[0, 0] = m[...] + jnp.log(jnp.maximum(l[...], 1e-30))


def flash_attention_fwd(
    q: jax.Array,  # (B, KVH, Sq, G, D)
    k: jax.Array,  # (B, KVH, Skv, D)
    v: jax.Array,  # (B, KVH, Skv, D)
    *,
    causal: bool,
    scale: float,
    block_q: int = BLOCK,
    block_k: int = BLOCK,
    q_offset: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (o (B,KVH,Sq,G,D), lse (B,KVH,Sq*G,LANES) f32).

    Row ``t*G + g`` of lse belongs to query ``t``, head ``g`` of the group;
    its LANES columns hold the same value.
    """
    B, KVH, Sq, G, D = q.shape
    Skv = k.shape[2]
    block_q, block_k = _blocks(Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k

    grid = (B, KVH, nq, nk)
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        g=G,
        q_offset=q_offset,
    )
    rows = block_q * G
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, G, D), lambda b, h, iq, ik: (b, h, iq, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, G, D), lambda b, h, iq, ik: (b, h, iq, 0, 0)),
            pl.BlockSpec((1, 1, rows, LANES), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, Sq, G, D), q.dtype),
            jax.ShapeDtypeStruct((B, KVH, Sq * G, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dk/dv kernel (q innermost), dq kernel (kv innermost)
# ---------------------------------------------------------------------------


def _bwd_tile(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *, scale, causal,
              block_q, block_k, g, q_offset, iq, ik, masked):
    """Recompute one (q tile, kv tile) pair: returns (q, k, do, p, ds)."""
    rows = block_q * g
    D = q_ref.shape[-1]
    q = q_ref[0, 0].reshape(rows, D).astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].reshape(rows, D).astype(jnp.float32)
    o = o_ref[0, 0].reshape(rows, D).astype(jnp.float32)
    lse = lse_ref[0, 0][:, :1]  # (rows, 1)
    delta = jnp.sum(o * do, axis=-1, keepdims=True)  # (rows, 1)

    s = jax.lax.dot_general(
        q * scale, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if masked:
        s = _causal_mask(s, block_q=block_q, block_k=block_k, g=g, iq=iq, ik=ik,
                         q_offset=q_offset)
    p = jnp.exp(s - lse)  # (rows, Bk) — true softmax probs
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return q, k, do, p, p * (dp - delta)


def _bwd_dkv_kernel(
    q_ref,  # (1, 1, Bq, G, D)
    k_ref,  # (1, 1, Bk, D)
    v_ref,  # (1, 1, Bk, D)
    o_ref,  # (1, 1, Bq, G, D)
    do_ref,  # (1, 1, Bq, G, D)
    lse_ref,  # (1, 1, Bq*G, LANES)
    dk_ref,  # (1, 1, Bk, D)
    dv_ref,  # (1, 1, Bk, D)
    dk_acc,  # VMEM (Bk, D) f32
    dv_acc,  # VMEM (Bk, D) f32
    **common,
):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked: bool):
        q, _, do, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, iq=iq, ik=ik,
            masked=masked, **common
        )
        # dv += p^T @ do ; dk += ds^T @ q * scale
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_acc[...] += common["scale"] * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _run_live_tile(_compute, iq, ik, **_geom(common))

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref,  # (1, 1, Bq, G, D)
    k_ref,  # (1, 1, Bk, D)
    v_ref,  # (1, 1, Bk, D)
    o_ref,  # (1, 1, Bq, G, D)
    do_ref,  # (1, 1, Bq, G, D)
    lse_ref,  # (1, 1, Bq*G, LANES)
    dq_ref,  # (1, 1, Bq, G, D)
    dq_acc,  # VMEM (Bq*G, D) f32
    **common,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute(masked: bool):
        _, k, _, _, ds = _bwd_tile(
            q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, iq=iq, ik=ik,
            masked=masked, **common
        )
        dq_acc[...] += common["scale"] * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _run_live_tile(_compute, iq, ik, **_geom(common))

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].reshape(dq_ref.shape[2:]).astype(dq_ref.dtype)


def flash_attention_bwd(
    q, k, v, o, lse, do,
    *,
    causal: bool,
    scale: float,
    block_q: int = BLOCK,
    block_k: int = BLOCK,
    q_offset: int = 0,
    interpret: bool = False,
):
    """Returns (dq, dk, dv) with the layouts of (q, k, v)."""
    B, KVH, Sq, G, D = q.shape
    Skv = k.shape[2]
    block_q, block_k = _blocks(Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k

    common = dict(
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        g=G, q_offset=q_offset,
    )
    rows = block_q * G
    # index maps take (b, h, outer, inner); q_of/kv_of pick the tile index
    q_spec = lambda q_of: pl.BlockSpec(
        (1, 1, block_q, G, D), lambda b, h, i, j: (b, h, q_of(i, j), 0, 0))
    kv_spec = lambda kv_of: pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, i, j: (b, h, kv_of(i, j), 0))
    lse_spec = lambda q_of: pl.BlockSpec(
        (1, 1, rows, LANES), lambda b, h, i, j: (b, h, q_of(i, j), 0))
    outer = lambda i, j: i
    inner = lambda i, j: j

    # dk/dv: grid (B, KVH, nk, nq) — kv tile outer, q tile inner
    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(B, KVH, nk, nq),
        in_specs=[
            q_spec(inner), kv_spec(outer), kv_spec(outer),
            q_spec(inner), q_spec(inner), lse_spec(inner),
        ],
        out_specs=[kv_spec(outer), kv_spec(outer)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_BWD_PARAMS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, o, do, lse)

    # dq: grid (B, KVH, nq, nk) — q tile outer, kv tile inner
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(B, KVH, nq, nk),
        in_specs=[
            q_spec(outer), kv_spec(inner), kv_spec(inner),
            q_spec(outer), q_spec(outer), lse_spec(outer),
        ],
        out_specs=[q_spec(outer)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)],
        compiler_params=_BWD_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, o, do, lse)[0]

    return dq, dkv[0], dkv[1]
