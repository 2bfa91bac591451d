"""Serving-path correctness: incremental decode must reproduce the
teacher-forced forward logits for every architecture family.

Tolerance note: the decode path keeps softmax probabilities in bf16 for the
value matmul (avoiding f32 copies of the whole KV shard — 2x HBM traffic on
the serving hot path), so logits differ from the f32-accumulated forward by
up to ~5e-2 on <1% of elements. 6e-2 bounds that quantization noise while
still catching any real cache/rotary/position bug (those produce O(1)
errors)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED
from repro.models.model_api import build_model
from repro.sharding.plan import make_plan
from repro.runtime.serve_step import pad_cache

# families with a full-sequence `forward` producing (B, S, V) logits
DECODE_ARCHS = [
    "granite-3-2b",     # dense GQA
    "qwen2-72b",        # dense GQA + qkv bias
    "rwkv6-1.6b",       # attention-free recurrence
    "olmoe-1b-7b",      # MoE
    "zamba2-7b",        # mamba2 hybrid
    "granite-4-h-micro",  # mamba2 + NoPE GQA layers: SSM state and KV caches
]


def _fwd_logits(cfg, model, params, tokens, plan):
    """Full-sequence logits via the family's forward."""
    fam = cfg.family
    if fam in ("dense", "vlm"):
        from repro.models import transformer as tfm

        return tfm.forward(cfg, params, tokens, plan)
    if fam == "moe":
        from repro.models import moe

        return moe.forward(cfg, params, tokens, plan)[0]
    if fam == "rwkv":
        from repro.models import rwkv6

        return rwkv6.forward(cfg, params, tokens, plan)
    if fam == "hybrid":
        from repro.models import mamba2

        return mamba2.forward(cfg, params, tokens, plan)
    if fam == "granite_hybrid":
        from repro.models import granite_hybrid

        return granite_hybrid.forward(cfg, params, tokens, plan)
    raise ValueError(fam)


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_incremental_decode_matches_forward(arch):
    cfg = ASSIGNED[arch].reduced()
    model = build_model(cfg)
    plan = make_plan(cfg, None)
    params = model.init(jax.random.key(0))
    # S and S+extra divisible by the SSM chunk (8 in reduced configs)
    B, S, extra = 2, 16, 8
    tokens = jax.random.randint(jax.random.key(1), (B, S + extra), 0, cfg.vocab, jnp.int32)

    # teacher-forced reference logits over the whole sequence
    ref_logits = _fwd_logits(cfg, model, params, tokens, plan)

    # prefill on the first S tokens, then decode the remaining `extra`
    last, cache = model.prefill(params, {"tokens": tokens[:, :S]}, plan)
    np.testing.assert_allclose(
        np.asarray(last, np.float32),
        np.asarray(ref_logits[:, S - 1, :], np.float32),
        atol=6e-2, rtol=6e-2,
        err_msg=f"{arch}: prefill last-logits mismatch",
    )
    cache = pad_cache(cache, extra)
    for i in range(extra):
        logits, cache = model.decode(
            params, {"token": tokens[:, S + i]}, cache, S + i, plan
        )
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(ref_logits[:, S + i, :], np.float32),
            atol=6e-2, rtol=6e-2,
            err_msg=f"{arch}: decode step {i} mismatch",
        )


def test_whisper_decode_matches_prefill_path():
    """Enc-dec: the decoder's incremental path must agree with its own
    prefill logits when re-prefilling the extended sequence."""
    cfg = ASSIGNED["whisper-base"].reduced()
    model = build_model(cfg)
    plan = make_plan(cfg, None)
    params = model.init(jax.random.key(0))
    B, S = 2, 8
    frames = jax.random.normal(jax.random.key(2), (B, cfg.n_frames, cfg.d_model)).astype(jnp.bfloat16)
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab, jnp.int32)

    last_ref, _ = model.prefill(
        params, {"tokens": tokens, "frames": frames}, plan
    )
    last, cache = model.prefill(
        params, {"tokens": tokens[:, :S], "frames": frames}, plan
    )
    cache = pad_cache(cache, 1)
    logits, _ = model.decode(
        params, {"token": tokens[:, S], "frames": frames}, cache, S, plan
    )
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), np.asarray(last_ref, np.float32),
        atol=6e-2, rtol=6e-2,
    )
