"""End-to-end train-loop integration: loss goes down, resume is exact."""
import argparse
import json

import numpy as np
import pytest

from repro.launch.train import build_argparser, run


def _args(**overrides):
    base = dict(
        arch="granite-3-2b", reduced=True, steps=20, batch=4, seq=32,
        grad_accum=1, lr=1e-3, warmup=5, seed=0, workers=2, max_queue_size=4,
        ckpt_dir="", ckpt_every=50, log_every=100, mesh="none", metrics_out="",
        layers=0,
        total_steps=20,  # pin the LR schedule across interrupted runs
    )
    base.update(overrides)
    return argparse.Namespace(**base)


def test_loss_decreases_over_training():
    # Compare 5-step window means, not single steps: per-batch losses on the
    # stochastic synthetic stream are noisy enough that first-vs-last single
    # steps flip sign across seeds (seed 0 happened to rise 5.840 -> 5.868
    # while seed 1 fell 5.948 -> 5.781 over the same 30 steps).
    r = run(_args(steps=30))
    assert r["tail_mean_loss"] < r["head_mean_loss"], r
    assert np.isfinite(r["final_loss"])


def test_resume_is_bit_identical_to_uninterrupted(tmp_path):
    """A run interrupted at step 10 and resumed must reach the same final
    loss as an uninterrupted run — data stream + optimizer are deterministic."""
    full = run(_args(steps=20, ckpt_dir=str(tmp_path / "full"), ckpt_every=100))

    part1 = run(_args(steps=10, ckpt_dir=str(tmp_path / "resume"), ckpt_every=10))
    part2 = run(_args(steps=20, ckpt_dir=str(tmp_path / "resume"), ckpt_every=100))
    assert part2["steps"] == 10  # resumed from 10
    np.testing.assert_allclose(part2["final_loss"], full["final_loss"], rtol=1e-5)


def test_reduced_resnet_batch_follows_the_config():
    """resnet_medium's dataset is ImageNet64 (64x64, 1000 classes); its
    reduced config is 32x32 with 10 classes, and the batch must match it."""
    r = run(_args(arch="resnet_medium", steps=2, batch=2))
    assert len(r["losses"]) == 2 and np.all(np.isfinite(r["losses"])), r


def test_layers_cuts_depth_and_is_recorded():
    r = run(_args(steps=2, layers=1))
    assert r["layers"] == 1 and np.isfinite(r["final_loss"]), r
    with pytest.raises(ValueError, match="resnet"):
        run(_args(arch="resnet_small", steps=1, layers=1))


def test_grad_accum_matches_full_batch():
    """grad_accum=2 over batch 8 == one step over the same batch 8 (same
    data), up to f32 accumulation order."""
    a = run(_args(steps=5, batch=8, grad_accum=1, seed=3))
    b = run(_args(steps=5, batch=8, grad_accum=2, seed=3))
    np.testing.assert_allclose(a["final_loss"], b["final_loss"], rtol=2e-3)


@pytest.mark.parametrize("mode", ["interpret", None])
def test_launcher_reports_flash_tiles_where_the_step_takes_the_kernels(
        monkeypatch, capsys, mode):
    """On the Pallas path (interpret mode stands in for the chip) the launcher
    prints and returns the kernels' tile classes at ``--seq``; on the XLA
    path it reports none."""
    from repro.models import attention

    monkeypatch.setattr(attention, "_kernel_mode", lambda: mode)
    r = run(_args(steps=1, batch=1, seq=1024, layers=1, workers=1))
    out = capsys.readouterr().out
    if mode is None:
        assert r["flash_tiles"] is None and "flash tiles" not in out
    else:
        assert r["flash_tiles"] == {"skipped": 1, "masked": 2, "unmasked": 1}
        assert "[train] flash tiles skipped 1 masked 2 unmasked 1" in out
