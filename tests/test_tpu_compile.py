"""Compile the main-path Pallas kernels, and one train step, for a TPU v5e at
published widths.

Nothing runs: the TPU compiler, which is installed with jaxlib, compiles for
a described ``v5e:2x2`` topology. That catches what interpret mode cannot:
block shapes off the (8, 128) tiling, layouts Mosaic cannot relayout, and
kernels over the scoped VMEM limit. The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and under several test workers an import-time description would
give the workers different tests to collect.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# (S, H, KVH, D) of granite-3-2b and llama3-8b attention; granite-3-2b also
# at the benchmark's 4096 context, where 8 of the 36 live tiles of each
# (batch row, KV head) take the masked body and 28 the unmasked one
FLASH_SHAPES = {
    "granite-3-2b": (2048, 32, 8, 64),
    "granite-3-2b-s4096": (4096, 32, 8, 64),
    "llama3-8b": (2048, 32, 8, 128),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch", sorted(FLASH_SHAPES))
def test_flash_attention_fwd_and_grad_compile(one_chip, arch):
    S, H, KVH, D = FLASH_SHAPES[arch]
    q = _sds(one_chip, (1, S, H, D))
    kv = _sds(one_chip, (1, S, KVH, D))

    def fwd(q, k, v):
        return ops.flash_attention(q, k, v, mode="tpu")

    grad = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))
    # the kernels keep their names in the compiled program, for a profile
    for f, names in ((fwd, ["flash_fwd"]), (grad, ["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"])):
        hlo = jax.jit(f).lower(q, kv, kv).compile().as_text()
        assert 'custom_call_target="tpu_custom_call"' in hlo
        assert all(n in hlo for n in names), names


def test_decode_attention_compiles_at_llama3_8b_widths(one_chip):
    B, Smax, H, KVH, D = 8, 32_768, 32, 8, 128
    cache = _sds(one_chip, (B, Smax, KVH, D))
    f = jax.jit(lambda q, k, v, n: ops.decode_attention(q, k, v, kv_len=n, mode="tpu"))
    hlo = f.lower(_sds(one_chip, (B, H, D)), cache, cache,
                  _sds(one_chip, (), jnp.int32)).compile().as_text()
    assert "%decode_attention" in hlo


def test_wkv6_compiles_at_rwkv6_1_6b_widths(one_chip):
    B, T, H, K = 1, 4096, 32, 64
    seq = _sds(one_chip, (B, T, H, K), jnp.float32)
    f = jax.jit(lambda r, k, v, w, u, s: ops.wkv6(r, k, v, w, u, s, chunk=64, mode="tpu"))
    hlo = f.lower(seq, seq, seq, seq, _sds(one_chip, (H, K), jnp.float32),
                  _sds(one_chip, (B, H, K, K), jnp.float32)).compile().as_text()
    assert "%rwkv6_scan" in hlo


def test_granite_train_step_compiles_with_pallas_attention(one_chip, monkeypatch):
    """The training step at granite's published widths (depth cut to two
    layers) takes the Pallas flash path and fits one chip's 16 GB."""
    from repro.configs.base import ShapeSuite
    from repro.configs.registry import get_config
    from repro.models import attention
    from repro.models.model_api import build_model
    from repro.optim import adamw
    from repro.runtime import train_step as ts
    from repro.sharding.plan import make_plan

    # the dispatch asks the process's backend (CPU here); steer it to the
    # kernel path the chip takes
    monkeypatch.setattr(attention, "_kernel_mode", lambda: "tpu")
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    model = build_model(cfg)
    opt = adamw.AdamWConfig()
    put = lambda tree: jax.tree_util.tree_map(
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    state = jax.eval_shape(lambda k: ts.init_train_state(model, k, opt), jax.random.key(0))
    batch = model.input_specs(ShapeSuite("t", 2048, 4, "train"))
    step = jax.jit(ts.build_train_step(model, make_plan(cfg, None), opt),
                   donate_argnums=(0,))
    compiled = step.lower(put(state), put(batch)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3
    # the step's ops carry its phases in their metadata
    assert 'op_name="jit(train_step)/forward_backward/' in hlo
    assert 'op_name="jit(train_step)/optimizer/' in hlo
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < 16e9, live
