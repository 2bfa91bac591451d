"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed under interpret=True (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import flash_attention as fa

KEY = jax.random.key(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention forward
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Skv, H, KVH, D, causal, dtype, bq, bk)
    (1, 64, 64, 4, 4, 32, True, jnp.float32, 16, 16),   # MHA
    (2, 128, 128, 8, 2, 64, True, jnp.float32, 32, 64),  # GQA g=4
    (2, 128, 128, 8, 1, 32, True, jnp.float32, 64, 32),  # MQA
    (1, 96, 96, 4, 4, 16, True, jnp.float32, 32, 32),    # non-pow2 seq
    (1, 64, 64, 4, 2, 32, False, jnp.float32, 16, 32),   # non-causal
    (2, 64, 64, 8, 4, 64, True, jnp.bfloat16, 32, 32),   # bf16 io
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_fwd_matches_oracle(case):
    B, Sq, Skv, H, KVH, D, causal, dtype, bq, bk = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, KVH, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, KVH, D), jnp.float32).astype(dtype)
    o_ref = ref.mha_reference(q, k, v, causal=causal)
    o_pal = ops.flash_attention(
        q, k, v, causal=causal, block_q=bq, block_k=bk, mode="interpret"
    )
    np.testing.assert_allclose(
        o_pal.astype(jnp.float32), o_ref.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize(
    "case", [(2, 64, 8, 2, 32, True), (1, 64, 4, 4, 16, True), (1, 64, 4, 2, 32, False)]
)
def test_flash_bwd_matches_oracle(case):
    B, S, H, KVH, D, causal = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KVH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KVH, D), jnp.float32)

    def loss_pal(q, k, v):
        o = ops.flash_attention(q, k, v, causal=causal, block_q=16, block_k=32,
                                mode="interpret")
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.mha_reference(q, k, v, causal=causal)))

    gp = jax.grad(loss_pal, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gp, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4, err_msg=name)


def test_flash_lse_is_true_logsumexp():
    B, S, H, KVH, D = 1, 32, 2, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KVH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KVH, D), jnp.float32)
    qf = ops._fold(q, KVH)
    _, lse = fa.flash_attention_fwd(
        qf, ops._kv_fold(k), ops._kv_fold(v), causal=True, scale=D**-0.5,
        block_q=8, block_k=8, interpret=True,
    )
    # oracle lse
    s = jnp.einsum("bqhd,bkhd->bhqk", q.reshape(B, S, KVH, D) * D**-0.5,
                   k) if KVH == H else None
    qs = (q.reshape(B, S, KVH, 1, D) * D**-0.5)
    scores = jnp.einsum("bqhgd,bkhd->bhqgk", qs, k)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scores = jnp.where(mask[None, None, :, None, :], scores, -1e30)
    want = jax.scipy.special.logsumexp(scores, axis=-1)  # (B,H,S,G)
    # lse rows are (query, group head) folded, each row lane-replicated
    assert lse.shape == (B, KVH, S * (H // KVH), fa.LANES)
    np.testing.assert_array_equal(lse, jnp.broadcast_to(lse[..., :1], lse.shape))
    np.testing.assert_allclose(
        lse[..., 0].reshape(B, KVH, S, H // KVH), want, atol=1e-4, rtol=1e-4
    )


# Tile layouts against the causal diagonal: the kernels skip the tiles above
# it, mask the tiles that straddle it and run the tiles below it unmasked.
TILE_LAYOUTS = [
    # (Sq, Skv, bq, bk, q_offset, causal)
    (128, 128, 32, 32, 0, True),   # bq = bk
    (128, 128, 16, 32, 0, True),   # bq < bk
    (128, 128, 64, 16, 0, True),   # bq > bk
    (64, 128, 16, 32, 64, True),   # queries after a prefix: q_offset > 0
    (128, 128, 32, 64, 0, False),  # non-causal: no masked tiles
]


def _tile_inputs(Sq, Skv, B=2, H=8, KVH=2, D=32):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, KVH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, KVH, D), jnp.float32)
    do = jax.random.normal(ks[3], (B, Sq, H, D), jnp.float32)
    return q, k, v, do


@pytest.mark.parametrize("layout", TILE_LAYOUTS)
def test_flash_tile_layouts_fwd_and_lse(layout):
    Sq, Skv, bq, bk, q_offset, causal = layout
    q, k, v, _ = _tile_inputs(Sq, Skv)
    B, _, H, D = q.shape
    KVH = k.shape[2]
    scale = D**-0.5
    o, lse = fa.flash_attention_fwd(
        ops._fold(q, KVH), ops._kv_fold(k), ops._kv_fold(v), causal=causal,
        scale=scale, block_q=bq, block_k=bk, q_offset=q_offset, interpret=True,
    )
    want = ref.mha_reference(q, k, v, causal=causal, q_offset=q_offset)
    np.testing.assert_allclose(ops._unfold(o), want, **_tol(jnp.float32))
    scores = jnp.einsum("bqhgd,bkhd->bhqgk", q.reshape(B, Sq, KVH, H // KVH, D) * scale, k)
    if causal:
        mask = (q_offset + jnp.arange(Sq))[:, None] >= jnp.arange(Skv)[None, :]
        scores = jnp.where(mask[None, None, :, None, :], scores, -1e30)
    want_lse = jax.scipy.special.logsumexp(scores, axis=-1)  # (B, KVH, Sq, G)
    np.testing.assert_array_equal(lse, jnp.broadcast_to(lse[..., :1], lse.shape))
    np.testing.assert_allclose(
        lse[..., 0].reshape(want_lse.shape), want_lse, atol=1e-4, rtol=1e-4
    )


@pytest.mark.parametrize("layout", TILE_LAYOUTS)
def test_flash_tile_layouts_bwd(layout):
    Sq, Skv, bq, bk, q_offset, causal = layout
    q, k, v, do = _tile_inputs(Sq, Skv)
    KVH, D = k.shape[2], q.shape[-1]
    kw = dict(causal=causal, scale=D**-0.5, block_q=bq, block_k=bk,
              q_offset=q_offset, interpret=True)
    qf, kf, vf = ops._fold(q, KVH), ops._kv_fold(k), ops._kv_fold(v)
    o, lse = fa.flash_attention_fwd(qf, kf, vf, **kw)
    dq, dk, dv = fa.flash_attention_bwd(qf, kf, vf, o, lse, ops._fold(do, KVH), **kw)
    _, vjp = jax.vjp(
        lambda q, k, v: ref.mha_reference(q, k, v, causal=causal, q_offset=q_offset),
        q, k, v,
    )
    want = vjp(do)
    for name, a, b in zip(("dq", "dk", "dv"),
                          (ops._unfold(dq), ops._kv_fold(dk), ops._kv_fold(dv)), want):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4, err_msg=name)


def _brute_tile_classes(Sq, Skv, bq, bk, q_offset, causal):
    """Classify each tile by the mask over its elements."""
    counts = {"skipped": 0, "masked": 0, "unmasked": 0}
    for iq in range(Sq // bq):
        q_pos = q_offset + iq * bq + np.arange(bq)
        for ik in range(Skv // bk):
            seen = q_pos[:, None] >= ik * bk + np.arange(bk)[None, :]
            if not causal:
                seen[:] = True
            kind = "unmasked" if seen.all() else "masked" if seen.any() else "skipped"
            counts[kind] += 1
    return counts


@pytest.mark.parametrize("layout", TILE_LAYOUTS + [(4096, 4096, 512, 512, 0, True)])
def test_tile_classes_match_brute_force(layout):
    Sq, Skv, bq, bk, q_offset, causal = layout
    got = fa.tile_classes(Sq, Skv, bq, bk, causal, q_offset)
    assert got == _brute_tile_classes(*layout)
    assert sum(got.values()) == (Sq // bq) * (Skv // bk)
    if causal:  # every causal layout above holds all three kinds
        assert min(got.values()) > 0, got
    else:
        assert got["masked"] == got["skipped"] == 0


def test_tile_classes_at_granite_l12():
    # seq 4096, the kernels' default blocks: the mask runs on 8 of 36 live tiles
    assert fa.tile_classes(4096, 4096, 512, 512, True) == {
        "skipped": 28, "masked": 8, "unmasked": 28}


@pytest.mark.parametrize("entry", ["fwd", "bwd"])
def test_flash_rejects_lengths_off_the_blocks(entry):
    B, KVH, S, G, D = 1, 2, 48, 2, 16
    q = jnp.zeros((B, KVH, S, G, D), jnp.float32)
    kv = jnp.zeros((B, KVH, S, D), jnp.float32)
    kw = dict(causal=True, scale=D**-0.5, block_q=32, block_k=32, interpret=True)
    with pytest.raises(ValueError, match="must divide blocks"):
        if entry == "fwd":
            fa.flash_attention_fwd(q, kv, kv, **kw)
        else:
            lse = jnp.zeros((B, KVH, S * G, fa.LANES), jnp.float32)
            fa.flash_attention_bwd(q, kv, kv, q, lse, q, **kw)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DECODE_CASES = [
    # (B, Smax, H, KVH, D, kv_len, bk)
    (2, 128, 8, 2, 32, 128, 32),
    (2, 128, 8, 2, 32, 77, 32),    # partial cache
    (1, 256, 4, 4, 64, 1, 64),     # single valid entry
    (3, 96, 6, 1, 16, 50, 32),     # MQA, odd sizes
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_matches_oracle(case):
    B, Smax, H, KVH, D, kv_len, bk = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Smax, KVH, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Smax, KVH, D), jnp.float32)
    o_ref = ref.decode_attention_reference(q, kc, vc, kv_len=kv_len)
    o_pal = ops.decode_attention(q, kc, vc, kv_len=kv_len, block_k=bk,
                                 mode="interpret")
    np.testing.assert_allclose(o_pal, o_ref, atol=2e-5, rtol=2e-5)


def test_decode_traced_kv_len():
    """kv_len must be traceable (it's a loop carry in the decode loop)."""
    B, Smax, H, KVH, D = 1, 64, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Smax, KVH, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Smax, KVH, D), jnp.float32)

    @jax.jit
    def f(kv_len):
        return ops.decode_attention(q, kc, vc, kv_len=kv_len, block_k=16,
                                    mode="interpret")

    for n in (1, 13, 64):
        np.testing.assert_allclose(
            f(jnp.int32(n)),
            ref.decode_attention_reference(q, kc, vc, kv_len=n),
            atol=2e-5, rtol=2e-5,
        )


# ---------------------------------------------------------------------------
# WKV6 chunked scan
# ---------------------------------------------------------------------------

WKV_CASES = [
    # (B, T, H, K, chunk, zero_state)
    (1, 64, 2, 16, 16, True),
    (2, 128, 4, 32, 32, True),
    (1, 96, 2, 16, 32, False),  # nonzero initial state, odd chunk count
    (2, 64, 2, 8, 64, True),    # single chunk
]


@pytest.mark.parametrize("case", WKV_CASES)
def test_wkv6_matches_oracle(case):
    B, T, H, K, chunk, zero_state = case
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, T, H, K), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K), jnp.float32) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, K), jnp.float32) * 0.2
    s0 = (
        jnp.zeros((B, H, K, K), jnp.float32)
        if zero_state
        else jax.random.normal(ks[5], (B, H, K, K), jnp.float32) * 0.3
    )
    o_ref, s_ref = ref.wkv6_reference(r, k, v, logw, u, s0)
    o_pal, s_pal = ops.wkv6(r, k, v, logw, u, s0, chunk=chunk, mode="interpret")
    np.testing.assert_allclose(o_pal, o_ref, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(s_pal, s_ref, atol=5e-5, rtol=5e-5)


def test_wkv6_strong_decay_is_stable():
    """Strong decay (|logw| large) must not overflow the chunked form."""
    B, T, H, K = 1, 64, 1, 8
    ks = jax.random.split(KEY, 3)
    r = jax.random.normal(ks[0], (B, T, H, K), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, H, K), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, K), jnp.float32)
    logw = jnp.full((B, T, H, K), -3.0)  # e^{-3} per step, e^{-192}/chunk
    u = jnp.zeros((H, K))
    s0 = jnp.zeros((B, H, K, K))
    o_pal, s_pal = ops.wkv6(r, k, v, logw, u, s0, chunk=64, mode="interpret")
    assert jnp.isfinite(o_pal).all() and jnp.isfinite(s_pal).all()
    o_ref, _ = ref.wkv6_reference(r, k, v, logw, u, s0)
    np.testing.assert_allclose(o_pal, o_ref, atol=5e-5, rtol=5e-5)


def test_model_chunked_wkv_matches_kernel():
    """The model's XLA chunked path and the Pallas kernel agree."""
    from repro.models.rwkv6 import wkv_chunked

    B, T, H, K = 1, 64, 2, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.3 - 2.0)
    u = jax.random.normal(ks[4], (H, K)) * 0.2
    s0 = jnp.zeros((B, H, K, K))
    o_x, s_x = wkv_chunked(r, k, v, logw, u, s0, chunk=16)
    o_p, s_p = ops.wkv6(r, k, v, logw, u, s0, chunk=16, mode="interpret")
    np.testing.assert_allclose(o_p, o_x, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(s_p, s_x, atol=5e-5, rtol=5e-5)


# ---------------------------------------------------------------------------
# calibration shapes (core/calib KernelBackend; ISSUE 10)
# ---------------------------------------------------------------------------

def test_calibration_shapes_match_ref_oracles():
    """Every kernel family's calibration shape runs interpret-mode on CPU
    and agrees with its pure-jnp oracle — the numerics bar the measured
    calibration backend stands on (benchmarks/kernel_bench.py)."""
    from benchmarks.kernel_bench import (
        CALIBRATION_KERNELS,
        CALIBRATION_SHAPES,
        measure_calibration_kernel,
    )

    # one representative arch per kernel family actually used in the map
    reps = {}
    for family, kernel in CALIBRATION_KERNELS.items():
        reps.setdefault(kernel, family)
    assert set(reps) <= set(CALIBRATION_SHAPES)
    archs = {"flash_attention": "llama3-8b", "wkv6": "rwkv6-1.6b"}
    for kernel in sorted(reps):
        arch = archs.get(kernel)
        if arch is None:
            continue
        meas = measure_calibration_kernel(arch, n=1)
        assert meas["kernel"] == kernel
        assert meas["wall_s"] > 0.0
        assert meas["max_err_vs_ref"] < 2e-4, (kernel, meas)
    # the serve-phase shape (no training arch maps to it) via the override
    meas = measure_calibration_kernel(
        "qwen2-72b", n=1, kernel="decode_attention"
    )
    assert meas["kernel"] == "decode_attention"
    assert meas["max_err_vs_ref"] < 2e-4, meas


def test_calibration_kernel_for_covers_registry():
    from benchmarks.kernel_bench import CALIBRATION_SHAPES, calibration_kernel_for
    from repro.configs.registry import CONFIGS

    for arch in CONFIGS:
        assert calibration_kernel_for(arch) in CALIBRATION_SHAPES
