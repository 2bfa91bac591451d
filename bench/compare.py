"""The comparison that decides ``correct``: the program's first three steps
of each job against the plain reference of its family (``refs/<family>.py``)
from the same seed and rows.

The numbers, for each job; a cell compares those its
``limits/<workload>.json`` names, each held to its limit there, and PERF.md
gives the readings each limit was set from:

- ``loss_gap``, ``loss1_gap``: the largest relative gap of the three steps'
  losses, and that of the first step's alone;
- ``gnorm_gap``, ``gnorm1_gap``: the same of the global gradient norm before
  clipping;
- ``grad_gap``: over leaves, the gap between the norms of the first
  gradient as the optimizer got it, over the larger of the reference
  leaf's norm and the median leaf's; ``grad_median_gap``: the median leaf's;
- ``change_gap``: the same of each leaf's change after three steps, over
  the leaves whose reference gradient is at least a thousandth of the median
  leaf's (the others move under Adam by rounding alone);
  ``change_median_gap``: the median leaf's;
- ``out_grad_gap``: the norm of the difference between the program's and
  the reference's first gradient (before clipping) of the output layer
  (``refs/<family>.py:OUT_LEAF``), over the reference's norm of it. The
  gaps of norms above are second order in rounding noise that is not
  correlated with the gradient; this one is first order.
"""
from __future__ import annotations

import functools
import importlib
import json

import numpy as np

import jax.numpy as jnp

import gen
from refs import common

NUMBERS = ("loss_gap", "loss1_gap", "gnorm_gap", "gnorm1_gap", "grad_gap", "grad_median_gap", "change_gap",
           "change_median_gap", "out_grad_gap")
STILL = 1e-3  # reference gradient under this share of the median leaf's


@functools.lru_cache(maxsize=None)
def _fns(family: str, cfg_json: str, lowp: bool):
    """(init, loss) of a family, made once so that jit caches hold across calls."""
    mod = importlib.import_module(f"refs.{family}")
    cfg = json.loads(cfg_json)
    return (functools.partial(mod.init, cfg), functools.partial(mod.loss, cfg, lowp))


def out_leaf(cfg_doc: dict) -> str:
    """Path of the output layer's weights in the family's tree."""
    return importlib.import_module(f"refs.{cfg_doc['family']}").OUT_LEAF


def placement(traffic: dict, devices):
    """Where the reference runs: on one device as it stands (None), or laid
    over the cell's devices (``common.spread``) where the traffic names a
    mesh, as its program is."""
    return common.spread(devices) if "mesh" in traffic else None


def reference(cfg_doc: dict, traffic: dict, seed: int, job: int, *, lowp=False,
              rows=None, place=None) -> dict:
    """Readings of the plain reference for one job, laid out by ``place``.
    For the control and the planted faults of calibrate.py: ``lowp`` rounds
    its products to float8, ``rows`` maps the three batches to what the
    reference is fed."""
    init, loss = _fns(cfg_doc["family"], json.dumps(cfg_doc, sort_keys=True), lowp)
    ds = gen.data_seed(seed, job)
    batches = [{k: jnp.asarray(v) for k, v in gen.batch(cfg_doc, traffic, ds, s).items()}
               for s in range(3)]
    if rows is not None:
        batches = rows(batches)
    return common.three_steps(init, loss, gen.job_key(seed, job), batches,
                              traffic["optimizer"], out_leaf(cfg_doc), place)


def _leaf_gaps(prog: dict, ref: dict, keep) -> dict:
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers for one job, with the leaf the worst gaps were at."""
    loss = [abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"])]
    med = float(np.median(list(ref["grad"].values())))
    moved = [k for k, g in ref["grad"].items() if g >= STILL * med]
    grad = _leaf_gaps(prog["grad"], ref["grad"], list(ref["grad"]))
    change = _leaf_gaps(prog["change"], ref["change"], moved)
    grad_leaf, change_leaf = max(grad, key=grad.get), max(change, key=change.get)
    gnorm = [abs(p - r) / r for p, r in zip(prog.get("gnorms", []), ref.get("gnorms", []))]
    out = float(np.linalg.norm(prog["out_grad"] - ref["out_grad"]) / np.linalg.norm(ref["out_grad"]))
    return {"loss_gap": max(loss), "loss1_gap": loss[0],
            "gnorm_gap": max(gnorm, default=float("nan")),
            "gnorm1_gap": gnorm[0] if gnorm else float("nan"),
            "grad_gap": grad[grad_leaf], "grad_median_gap": float(np.median(list(grad.values()))),
            "change_gap": change[change_leaf], "change_median_gap": float(np.median(list(change.values()))),
            "out_grad_gap": out, "grad_leaf": grad_leaf, "change_leaf": change_leaf}


def check(cfg_doc, traffic, seed, readings, limits, refs=None,
          place=None) -> tuple[bool, dict, list]:
    """Worst number over the cell's jobs, each beside its limit, and the
    numbers of each job. ``refs`` are the reference's readings where the
    caller has them already; else it runs, laid out by ``place``."""
    numbers = [n for n in NUMBERS if n in limits]
    worst = {n: 0.0 for n in numbers}
    per_job = []
    for job, prog in enumerate(readings):
        if not all(np.isfinite(prog["losses"])):
            worst = {n: float("inf") for n in numbers}
            break
        ref = (refs[job] if refs is not None
               else reference(cfg_doc, traffic, seed, job, place=place))
        g = gaps(prog, ref)
        per_job.append(g)
        worst = {n: max(worst[n], g[n]) for n in numbers}
    checks = {n: {"value": worst[n], "limit": limits[n]} for n in numbers}
    return all(worst[n] <= limits[n] for n in numbers), checks, per_job
