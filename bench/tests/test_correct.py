"""The comparison that decides ``correct``, at sizes a CPU test can hold.

A run is driven end to end by ``run.run_cell`` (the look for a chip is
skipped) on small configurations of each family. It must come out correct
as it stands, and not correct with the timed path broken underneath: a
step that returns its state unchanged, and a step that leaves out half of
each batch and takes the mean over the rest. The control (the reference
with its products rounded to float8, put in the program's place) must read
above the limits too.

The limits here are for these small sizes on the CPU, on the numbers each
family's cells compare (``bench/limits/``), set between the readings of the
sound program (4 seeds) and of the control and the half-batch fault (3
seeds), read with ``bench/calibrate.py``'s code on this backend:

- dense: sound loss <= 9.7e-5, gnorm <= 1.2e-3, grad <= 3.2e-4, change
  <= 1.6e-3, out_grad <= 1.05e-2; control grad >= 9.3e-3, out_grad >=
  0.135 (loss >= 9.8e-4, gnorm >= 5.5e-3); half batch loss >= 6.8e-3, gnorm
  >= 0.45, grad >= 5.3e-2, out_grad >= 0.99; state unchanged change 1,
  out_grad 1;
- resnet (float32 on the CPU, so the sound program is near exact): sound
  gnorm <= 1.8e-7, change <= 6.1e-6, out_grad <= 1e-7; control gnorm >=
  1.2e-3, change >= 7.6e-2, out_grad >= 3.2e-2; half batch gnorm >= 0.74,
  out_grad >= 0.60; state unchanged change 1, out_grad 1.

"""
import time

import jax
import pytest

import compare
import run
from repro.runtime import train_step as ts

OPT = {"lr_peak": 3e-4, "warmup_steps": 0, "total_steps": 10000}
SMALL = {
    "dense": ({"name": "small", "arch": "granite-3-2b", "family": "dense", "hidden_size": 128,
               "intermediate_size": 512, "num_attention_heads": 8, "num_key_value_heads": 2,
               "num_hidden_layers": 2, "vocab_size": 1024, "rope_theta": 10000.0,
               "rms_norm_eps": 1e-6, "tie_word_embeddings": True, "z_loss": 1e-4},
              {"name": "t", "jobs": 1, "seq_len": 128, "batch": 2, "workers": 1,
               "max_queue_size": 10, "optimizer": OPT},
              {"loss_gap": 5e-4, "gnorm_gap": 0.1, "grad_gap": 3e-3, "change_gap": 0.3,
               "out_grad_gap": 0.04}),
    "resnet": ({"name": "small", "arch": "resnet_medium", "family": "resnet", "image_size": 16,
                "num_classes": 10, "stages": [1, 1], "base_width": 8},
               {"name": "t", "jobs": 2, "batch": 8, "workers": 1, "max_queue_size": 10,
                "optimizer": OPT},
               {"gnorm_gap": 1e-4, "change_gap": 1e-3, "out_grad_gap": 1e-3}),
}
SEED = 3_000_000_019  # above 2**31: seeds take more than 32 signed bits


def _cell(family):
    cfg, traffic, limits = SMALL[family]
    return {"cfg": cfg, "traffic": traffic, "limits": limits, "end_to_end": [], "per_layer": []}


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


@pytest.mark.parametrize("family", sorted(SMALL))
@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_run_is_correct_only_when_sound(family, fault, monkeypatch):
    if fault is not None:
        monkeypatch.setattr(ts, "build_train_step", fault(ts.build_train_step))
    r = run.run_cell(_cell(family), SEED, 0.5, False, t0=time.perf_counter())
    assert r["correct"] is (fault is None), r["checks"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("family", sorted(SMALL))
def test_control_fails_the_limits(family):
    """The control, put in the program's place, through the comparison."""
    c = _cell(family)
    low = compare.reference(c["cfg"], c["traffic"], SEED, 0, lowp=True)
    ok, checks, _ = compare.check(c["cfg"], c["traffic"], SEED, [low], c["limits"])
    assert not ok, checks
