"""The FLOP counters of ``bench/flops`` against XLA's own count
(``cost_analysis``) of the program's train step, compiled for the CPU
without remat at small sizes whose matmuls and convolutions dominate.

XLA also counts what the counters leave out on purpose (normalisation,
softmax, activations, the optimizer's elementwise update), so its count
may lie above, by no more than ``SLACK``. On the CPU path attention takes
XLA's blocked kernel, which computes every query-key pair and masks; the
counter's causal pairs are swapped for all pairs before comparing.
"""
import dataclasses

import jax
import pytest

import jobs
from flops import dense, resnet
from repro.runtime import train_step as ts
from repro.sharding.plan import make_plan

SLACK = 0.08
OPT = {"lr_peak": 3e-4, "warmup_steps": 0, "total_steps": 100}

DENSE = {"arch": "granite-3-2b", "family": "dense", "hidden_size": 256,
         "intermediate_size": 1024, "num_attention_heads": 8, "num_key_value_heads": 2,
         "num_hidden_layers": 1, "vocab_size": 512, "tie_word_embeddings": True}
RESNET = {"arch": "resnet_medium", "family": "resnet", "image_size": 64, "num_classes": 100,
          "stages": [1, 1], "base_width": 32}


def xla_flops(cfg_doc, traffic):
    cfg = dataclasses.replace(jobs.model_config(cfg_doc), remat=False)
    model = jobs.build_model(cfg)
    opt = jobs.opt_config(traffic)
    suite = jobs.ShapeSuite("t", traffic.get("seq_len", 0), traffic["batch"], "train")
    state = jax.eval_shape(lambda k: ts.init_train_state(model, k, opt), jax.random.key(0))
    step = jax.jit(ts.build_train_step(model, make_plan(cfg, None), opt))
    return step.lower(state, model.input_specs(suite)).compile().cost_analysis()["flops"]


def test_dense_step_flops_match_xla():
    traffic = {"batch": 2, "seq_len": 256, "optimizer": OPT}
    causal = dense.attention_fwd_flops(DENSE, traffic)
    s = traffic["seq_len"]
    ours = dense.step_flops(DENSE, traffic) + 3 * causal * (s * s / (s * (s + 1) / 2) - 1)
    theirs = xla_flops(DENSE, traffic)
    assert ours <= theirs <= ours * (1 + SLACK), (ours, theirs)


def test_resnet_step_flops_match_xla():
    traffic = {"batch": 4, "optimizer": OPT}
    ours = resnet.step_flops(RESNET, traffic)
    theirs = xla_flops(RESNET, traffic)
    assert ours <= theirs <= ours * (1 + SLACK), (ours, theirs)


def test_flash_calls_cover_the_step_attention():
    """The kernel calls of a step carry the attention's model FLOPs plus the
    remat forward and the scores recomputed in the backward."""
    traffic = {"batch": 2, "seq_len": 4096}
    cfg = dict(DENSE, num_hidden_layers=3)
    calls = dense.kernel_calls(cfg, traffic, "flash")
    assert len(calls) == 3 * 3
    fwd = dense.attention_fwd_flops(cfg, traffic)
    assert sum(f for f, _ in calls) == pytest.approx(fwd * (2 + 5 / 2))
