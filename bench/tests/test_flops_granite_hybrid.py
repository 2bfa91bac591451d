"""``flops/granite_hybrid.py`` against XLA's own count (``cost_analysis``)
of the program's granite-4.0-h train step, compiled for the CPU without
remat at a small size with the registry's Mamba-2 widths (head 64, state
128, chunk 256), and its kernel calls against its model FLOPs. XLA counts
a loop's body once, so the step compared has one layer of each kind and
one chunk of tokens.

As ``test_flops.py`` does for the dense step: XLA also counts what the
counter leaves out on purpose (norms, the conv, activations, the SSD's
decays, the optimizer), by no more than ``SLACK``; on the CPU, attention
and the SSD's chunk products compute every pair and mask, so the
counter's causal pairs are swapped for all pairs before comparing.
"""
import dataclasses
import json
from pathlib import Path

import jax
import pytest

import jobs
from flops import dense, granite_hybrid as gh
from repro.runtime import train_step as ts
from repro.sharding.plan import make_plan

SLACK = 0.08
BENCH = Path(__file__).resolve().parents[1]
CFG = dict(json.loads((BENCH / "configs/granite-4-h-micro-l10.json").read_text()),
           hidden_size=256, intermediate_size=1024, num_attention_heads=8,
           num_key_value_heads=2, mamba_n_heads=8, num_hidden_layers=2, vocab_size=512,
           layer_types=["mamba", "attention"])
TRAFFIC = {"batch": 2, "seq_len": 256, "optimizer": {"lr_peak": 3e-4, "warmup_steps": 0,
                                                     "total_steps": 100}}


def test_step_flops_match_xla():
    cfg = dataclasses.replace(jobs.model_config(CFG), remat=False,
                              layer_types=tuple(CFG["layer_types"]))
    model = jobs.build_model(cfg)
    opt = jobs.opt_config(TRAFFIC)
    suite = jobs.ShapeSuite("t", TRAFFIC["seq_len"], TRAFFIC["batch"], "train")
    state = jax.eval_shape(lambda k: ts.init_train_state(model, k, opt), jax.random.key(0))
    step = jax.jit(ts.build_train_step(model, make_plan(cfg, None), opt))
    theirs = step.lower(state, model.input_specs(suite)).compile().cost_analysis()["flops"]

    n = gh.counts(CFG)
    s, C = TRAFFIC["seq_len"], CFG["mamba_chunk_size"]
    H, P, N = CFG["mamba_n_heads"], CFG["mamba_d_head"], CFG["mamba_d_state"]
    all_pairs = 3 * dense.attention_fwd_flops(dict(CFG, num_hidden_layers=n["attention"]), TRAFFIC) \
        * (s * s / (s * (s + 1) / 2) - 1)
    chunk_pairs = 3 * n["mamba"] * TRAFFIC["batch"] * (s // C) * 2 * (N + H * P) \
        * (C * C - C * (C + 1) // 2)
    ours = gh.step_flops(CFG, TRAFFIC) + all_pairs + chunk_pairs
    assert ours <= theirs <= ours * (1 + SLACK), (ours, theirs)


def test_kernel_calls_cover_the_step_attention_and_scan():
    traffic = {"batch": 2, "seq_len": 4096}
    cfg = dict(CFG, num_hidden_layers=10,
               layer_types=json.loads((BENCH / "configs/granite-4-h-micro-l10.json").read_text())["layer_types"])
    n = gh.counts(cfg)
    assert n == {"mamba": 9, "attention": 1}
    flash = gh.kernel_calls(cfg, traffic, "flash")
    fwd = dense.attention_fwd_flops(dict(cfg, num_hidden_layers=1), traffic)
    assert len(flash) == 2 and sum(f for f, _ in flash) == pytest.approx(fwd * (1 + 5 / 2))
    ssd = gh.kernel_calls(cfg, traffic, "ssd")
    assert len(ssd) == 3 * 9
    assert sum(f for f, _ in ssd) == pytest.approx(9 * 4 * gh.ssd_fwd_flops(cfg, traffic))


# op texts of the step compiled for a v5e (shortened after the operands'
# first types): the SSD's loops forward and backward, a layer loop, the
# flash kernels, another Pallas call, a tuple element named for its loop;
# and two loops as a chip trace prints them, with the operand's type after
# the opcode: the state past the fifth element, and a layer loop
OPS = {
    "%while.433 = (s32[]{:T(128)}, bf16[16,2,64,256,64]{3,4,2,1,0}, f32[16,2,64,256,1]{4,3,2,1,0}, "
    "f32[16,2,64,256,1]{4,3,2,1,0}, bf16[16,2,256,128]{3,2,1,0}, /*index=5*/f32[2,64,64,128]{3,2,1,0}) "
    "while((s32[]{:T(128)}, bf16[16,2,64,256,64]{3,4,2,1,0}, f32[16,2,64,256,1]{4,3,2,1,0}, "
    "f32[16,2,64,256,1]{4,3,2,1,0}, bf16[16,2,256,128]{3,2,1,0}, /*index=5*/f32[2,64,64,128]{3,2,1,0}) "
    "%tuple.9), condition=%c, body=%b": "ssd",
    "%while.439 = (s32[]{:T(128)}, bf16[2,4096,2048]{1,2,0}, f32[5,64]{1,0}, f32[5,64]{1,0}, "
    "bf16[5,4352]{1,0}, /*index=5*/bf16[5,4,4352]{2,1,0}, bf16[5,2,4096,2048]{2,3,1,0}) "
    "while((s32[]{:T(128)}, bf16[2,4096,2048]{1,2,0}, f32[5,64]{1,0}, f32[5,64]{1,0}, "
    "bf16[5,4352]{1,0}, /*index=5*/bf16[5,4,4352]{2,1,0}, bf16[5,2,4096,2048]{2,3,1,0}) "
    "%tuple.8), condition=%c, body=%b": None,
    "%while.422 = (s32[]{:T(128)}, f32[2,64,64,128]{3,2,1,0:T(8,128)S(1)}, "
    "f32[16,2,64,256,64]{3,4,2,1,0:T(8,128)}) while(%tuple.1), condition=%c, body=%b": "ssd",
    "%while.440 = (s32[]{:T(128)}, f32[2,64,64,128]{3,2,1,0:T(8,128)S(1)}, /*index=5*/"
    "f32[16,2,64,256]{3,2,1,0:T(8,128)S(1)}) while(%tuple.2), condition=%c, body=%b": "ssd",
    "%while.442 = (s32[]{:T(128)}, bf16[2,4096,2048]{1,2,0:T(8,128)(2,1)S(1)}, "
    "f32[5,64]{1,0:T(8,128)}) while(%tuple.3), condition=%c, body=%b": None,
    "%while.426 = f32[16,2,64,256]{3,2,1,0:T(8,128)S(1)} get-tuple-element(%while.424), "
    "index=3": None,
    "%ssd_fwd.3 = f32[2,4096,64,64]{3,2,1,0} custom-call(%a), "
    'custom_call_target="tpu_custom_call"': "ssd",
    "%flash_fwd.4 = (bf16[2,8,4096,4,64]{4,3,2,1,0:T(4,128)(2,1)}) custom-call(%bitcast.2189), "
    'custom_call_target="tpu_custom_call"': "flash",
    "%flash_bwd_dkv.2 = (bf16[2,8,4096,64]{3,2,1,0}) custom-call(%bitcast.2190), "
    'custom_call_target="tpu_custom_call"': "flash",
    "%flash_bwd_dq.2 = bf16[2,8,4096,4,64]{4,3,2,1,0} custom-call(%bitcast.2191), "
    'custom_call_target="tpu_custom_call"': "flash",
    '%fusion.12 = bf16[2,4096,2048]{2,1,0} fusion(%flash_fwd.4), kind=kLoop': None,
}


@pytest.mark.parametrize("op", list(OPS), ids=[o.split(" = ")[0] for o in OPS])
def test_kernel_patterns_match_their_ops_only(op):
    assert [k for k, p in gh.KERNELS.items() if p.search(op)] == ([OPS[op]] if OPS[op] else [])


@pytest.mark.parametrize("loops,ok", [(6, True), (5, False), (7, False), (0, True)])
def test_check_ssd_loops_wants_three_loops_for_each_run_of_mamba_layers(loops, ok):
    cfg = json.loads((BENCH / "configs/granite-4-h-micro-l10.json").read_text())
    assert gh.mamba_runs(cfg) == 2
    names = {f"%while.{400 + i}" for i in range(loops)} | {"%ssd_fwd.1"}
    if ok:
        gh.check_ssd_loops(cfg, names)
    else:
        with pytest.raises(RuntimeError, match="runs of Mamba layers"):
            gh.check_ssd_loops(cfg, names)
