"""Unified model API: one ``Model`` facade per architecture family.

Every family exposes the same surface so the runtime, collocation scheduler,
dry-run, and benchmarks never branch on architecture:

  init(key)                       -> params pytree
  loss(params, batch, plan)       -> (scalar, metrics)
  prefill(params, batch, plan)    -> (last_logits, cache)
  decode(params, batch, cache, pos, plan) -> (logits, cache)
  cache_spec(batch, seq)          -> ShapeDtypeStruct pytree
  input_specs(suite)              -> dict[str, ShapeDtypeStruct]
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSuite
from repro.models import losses
from repro.models import transformer as tfm
from repro.sharding.plan import ShardingPlan

Params = Dict[str, Any]


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Params]
    loss: Callable[..., Any]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    cache_spec: Callable[[int, int], Any]
    input_specs: Callable[[ShapeSuite], Dict[str, jax.ShapeDtypeStruct]]

    def param_count(self, params: Optional[Params] = None) -> int:
        from repro.models.module import param_count

        if params is None:
            params = jax.eval_shape(self.init, jax.random.key(0))
        return param_count(params)


# ---------------------------------------------------------------------------
# shared input-spec builders
# ---------------------------------------------------------------------------


def _lm_train_specs(cfg: ModelConfig, suite: ShapeSuite):
    B, S = suite.global_batch, suite.seq_len
    specs = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    if cfg.n_patches:
        specs["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.n_patches, cfg.d_model), jnp.bfloat16
        )
    if cfg.enc_layers:
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.n_frames, cfg.d_model), jnp.bfloat16
        )
    return specs


def _lm_prefill_specs(cfg: ModelConfig, suite: ShapeSuite):
    specs = _lm_train_specs(cfg, suite)
    specs.pop("labels")
    return specs


def _lm_decode_specs(cfg: ModelConfig, suite: ShapeSuite):
    B = suite.global_batch
    specs = {"token": jax.ShapeDtypeStruct((B,), jnp.int32)}
    if cfg.enc_layers:
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.n_frames, cfg.d_model), jnp.bfloat16
        )
    return specs


def _input_specs(cfg: ModelConfig, suite: ShapeSuite):
    if suite.kind == "train":
        return _lm_train_specs(cfg, suite)
    if suite.kind == "prefill":
        return _lm_prefill_specs(cfg, suite)
    return _lm_decode_specs(cfg, suite)


# ---------------------------------------------------------------------------
# dense / vlm families (transformer.py backbone)
# ---------------------------------------------------------------------------


def _build_dense(cfg: ModelConfig) -> Model:
    def init(key):
        return tfm.init_params(cfg, key)

    def loss(params, batch, plan: ShardingPlan):
        logits = tfm.forward(
            cfg, params, batch["tokens"], plan, patches=batch.get("patches")
        )
        return losses.softmax_cross_entropy(
            logits, batch["labels"], label_smoothing=cfg.label_smoothing
        )

    def prefill(params, batch, plan: ShardingPlan):
        return tfm.prefill(
            cfg, params, batch["tokens"], plan, patches=batch.get("patches")
        )

    def decode(params, batch, cache, pos, plan: ShardingPlan):
        return tfm.decode_step(cfg, params, batch["token"], cache, pos, plan)

    return Model(
        cfg=cfg,
        init=init,
        loss=loss,
        prefill=prefill,
        decode=decode,
        cache_spec=lambda b, s: tfm.cache_spec(cfg, b, s),
        input_specs=lambda suite: _input_specs(cfg, suite),
    )


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_BUILDERS: Dict[str, Callable[[ModelConfig], Model]] = {}


def register_family(name: str):
    def deco(fn):
        _BUILDERS[name] = fn
        return fn

    return deco


register_family("dense")(_build_dense)
register_family("vlm")(_build_dense)  # llava backbone = dense + patch stub


def build_model(cfg: ModelConfig) -> Model:
    # late imports so optional families register themselves
    from repro.models import moe as _moe  # noqa: F401
    from repro.models import rwkv6 as _rwkv6  # noqa: F401
    from repro.models import mamba2 as _mamba2  # noqa: F401
    from repro.models import granite_hybrid as _granite_hybrid  # noqa: F401
    from repro.models import encdec as _encdec  # noqa: F401
    from repro.models import resnet as _resnet  # noqa: F401

    if cfg.family not in _BUILDERS:
        raise KeyError(f"unknown family {cfg.family!r}")
    return _BUILDERS[cfg.family](cfg)
