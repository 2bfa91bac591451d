"""The comparison that decides ``correct`` for the granite-4.0-h family
(``refs/granite_hybrid.py``), at a size a CPU test can hold.

As ``test_correct.py`` does for the other families: ``run.run_cell`` drives
a run end to end (the look for a chip skipped), which must come out correct
as it stands, and not correct with a step that returns its state unchanged
or that leaves out half of each batch; the control (the reference with its
products rounded to float8, put in the program's place) must read above
the limits too. The configuration keeps the registry's Mamba-2 widths
(head 64, state 128, chunk 256) on a hidden size of 64, so that sequences of
512 tokens make two chunks, and six layers, so that one of them (layer 5)
is attention.

Limits, on numbers the cell compares (``bench/limits/``), set between the
readings of the sound program and of the control and the half-batch fault
(3 seeds each), read with ``bench/calibrate.py``'s code on the CPU: sound
loss <= 6.0e-6, gnorm1 <= 1.1e-4, change <= 4.7e-3, out_grad <= 7.1e-3;
control out_grad >= 2.75e-2 (loss >= 1.4e-5, change >= 5.5e-3); half batch
loss >= 4.6e-4, gnorm1 >= 0.41, change >= 4.2e-2, out_grad >= 0.99; state
unchanged change 1, out_grad 1. ``grad_gap`` is left out here: the bfloat16
program reads up to 3.8e-3 at this size, the control from 3.7e-3.
"""
import json
import time
from pathlib import Path

import jax
import pytest

import compare
import run
from repro.runtime import train_step as ts

BENCH = Path(__file__).resolve().parents[1]
OPT = {"lr_peak": 3e-4, "warmup_steps": 0, "total_steps": 10000}
CFG = dict(json.loads((BENCH / "configs/granite-4-h-micro-l10.json").read_text()),
           name="small", hidden_size=64, intermediate_size=128, num_attention_heads=4,
           num_key_value_heads=2, mamba_n_heads=2, num_hidden_layers=6, vocab_size=512)
TRAFFIC = {"name": "t", "jobs": 1, "seq_len": 512, "batch": 2, "workers": 1,
           "max_queue_size": 10, "optimizer": OPT}
LIMITS = {"loss_gap": 1e-4, "gnorm1_gap": 0.02, "change_gap": 0.02, "out_grad_gap": 0.015}
SEED = 3_000_000_019  # above 2**31: seeds take more than 32 signed bits


def _cell():
    return {"cfg": CFG, "traffic": TRAFFIC, "limits": LIMITS, "end_to_end": [], "per_layer": []}


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(run, "peaks", lambda: {"cpu": {"bf16_flops_per_s": 1e12,
                                                       "hbm_bytes_per_s": 1e11}})


def _state_unchanged(build):
    def make(*a, **k):
        real = build(*a, **k)
        return lambda state, batch: (state, real(state, batch)[1])
    return make


def _half_batch(build):
    def make(*a, **k):
        real = build(*a, **k)
        half = lambda x: x[: x.shape[0] // 2]  # noqa: E731
        return lambda state, batch: real(state, jax.tree_util.tree_map(half, batch))
    return make


def test_the_small_configuration_has_both_kinds():
    assert CFG["layer_types"][:CFG["num_hidden_layers"]] == ["mamba"] * 5 + ["attention"]
    assert TRAFFIC["seq_len"] == 2 * CFG["mamba_chunk_size"]


@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_run_is_correct_only_when_sound(fault, monkeypatch):
    if fault is not None:
        monkeypatch.setattr(ts, "build_train_step", fault(ts.build_train_step))
    r = run.run_cell(_cell(), SEED, 0.5, False, t0=time.perf_counter())
    assert r["correct"] is (fault is None), r["checks"]


def test_control_fails_the_limits():
    """The control, put in the program's place, through the comparison."""
    c = _cell()
    low = compare.reference(c["cfg"], c["traffic"], SEED, 0, lowp=True)
    ok, checks, _ = compare.check(c["cfg"], c["traffic"], SEED, [low], c["limits"])
    assert not ok, checks
