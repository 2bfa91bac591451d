"""Inputs and keys of a cell, made from ``--seed`` alone.

The batch generator is a copy of ``repro.data.synthetic.batch_for`` (the
same NumPy streams, so the same rows), kept here so that the yardstick and
the plain references never call the program to learn what was fed to it.
"""
from __future__ import annotations

import numpy as np

import jax

_U32 = 0xFFFFFFFF


def job_key(seed: int, job: int) -> jax.Array:
    """Parameter key of one job: every bit of a 64-bit seed reaches it."""
    base = jax.random.wrap_key_data(
        np.array([(seed >> 32) & _U32, seed & _U32], np.uint32))
    return jax.random.fold_in(base, job)


def data_seed(seed: int, job: int) -> int:
    """Data stream of one job: jobs of one run never share rows."""
    return int(np.random.SeedSequence([seed, job]).generate_state(1, np.uint64)[0])


def batch(cfg: dict, traffic: dict, seed: int, step: int) -> dict:
    """Rows of one step: tokens shifted by one for an LM, images and labels
    for an image classifier."""
    g = np.random.default_rng(np.random.SeedSequence([seed, 0, step]))
    b = traffic["batch"]
    if "image_size" in cfg:
        s = cfg["image_size"]
        return {
            "images": g.standard_normal((b, s, s, 3), dtype=np.float32),
            "labels": g.integers(0, cfg["num_classes"], (b,), dtype=np.int32),
        }
    stream = g.integers(0, cfg["vocab_size"], (b, traffic["seq_len"] + 1),
                        dtype=np.int32)
    return {"tokens": stream[:, :-1], "labels": stream[:, 1:]}
