"""The harness's multi-chip path, and its one-chip path unchanged, at sizes a
CPU test can hold.

On four virtual CPU devices (a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``), a small dense cell
on a 2x2 (data x model) mesh is driven end to end by ``run.run_cell``: it
comes out correct against the reference laid over the four devices
(``refs/common.py:spread``); that reference reads what the reference on one
device reads, to float32 rounding; and the step with half of each batch left
out, the step with the exchange between chips left out, and the control do
not come out correct.

On one device, the step that ``jobs.Cell`` builds for a one-chip cell
compiles to the same HLO as the step built by the calls the harness made
before it had a mesh path.
"""
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax

import jobs
from test_correct import SEED, SMALL

MESH = {"shape": [2, 2], "axes": ["data", "model"]}


def _mesh_cell():
    cfg, traffic, limits = SMALL["dense"]
    return {"cfg": cfg, "traffic": dict(traffic, mesh=MESH), "limits": limits,
            "end_to_end": [], "per_layer": []}


def _child() -> dict:
    """Run in the child process: the readings the tests below judge."""
    from jax.sharding import PartitionSpec as P

    import compare
    import run
    from refs import common
    from repro.runtime import train_step as ts
    from repro.sharding.plan import make_plan

    run.peaks = lambda: {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    c = _mesh_cell()
    out = {"devices": len(jax.devices())}
    r = run.run_cell(c, SEED, 0.5, False, t0=time.perf_counter())
    out["sound"] = {"correct": r["correct"], "checks": r["checks"]}

    cfg, traffic = c["cfg"], c["traffic"]
    one = compare.reference(cfg, traffic, SEED, 0)
    spread = compare.reference(cfg, traffic, SEED, 0, place=common.spread(jax.devices()))
    pairs = {k: (one[k], spread[k]) for k in ("losses", "gnorms")}
    pairs.update({k: (list(one[k].values()), [spread[k][n] for n in one[k]])
                  for k in ("grad", "change")})
    out["reference"] = {k: float(np.max(np.abs(np.subtract(a, b)) / np.abs(a)))
                        for k, (a, b) in pairs.items()}
    out["reference"]["out_grad"] = float(np.linalg.norm(one["out_grad"] - spread["out_grad"])
                                         / np.linalg.norm(one["out_grad"]))

    low = compare.reference(cfg, traffic, SEED, 0, lowp=True, place=common.spread(jax.devices()))
    ok, checks, _ = compare.check(cfg, traffic, SEED, [low], c["limits"], refs=[spread])
    out["control"] = {"correct": ok, "checks": checks}

    real = ts.build_train_step

    def half_batch(*a, **k):
        step = real(*a, **k)
        return lambda state, batch: step(
            state, jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch))

    def no_exchange(model, plan, opt, **k):
        """Each data shard steps on its own rows and nothing crosses the
        mesh: every chip keeps the slices of the state it updated alone."""
        step = real(model, make_plan(model.cfg, None), opt, **k)
        return jax.shard_map(step, mesh=plan.mesh, in_specs=(P(), P("data")),
                             out_specs=(P(), P()), check_vma=False)

    for name, fault in (("half_batch", half_batch), ("no_exchange", no_exchange)):
        ts.build_train_step = fault
        r = run.run_cell(c, SEED, 0.5, False, t0=time.perf_counter())
        out[name] = {"correct": r["correct"], "checks": r["checks"]}
    ts.build_train_step = real
    return out


@pytest.fixture(scope="module")
def on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__], env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_cell_is_correct_against_the_spread_reference(on_four_devices):
    assert on_four_devices["devices"] == 4
    assert on_four_devices["sound"]["correct"] is True, on_four_devices["sound"]["checks"]


def test_spread_reference_reads_as_on_one_device(on_four_devices):
    """Only the order of float32 sums differs: the largest relative gap of
    each reading (losses, norms per leaf), and of the output layer's whole
    gradient. The change after three AdamW steps divides by the root of the
    second moment, which magnifies rounding in a leaf's small gradients."""
    gaps = dict(on_four_devices["reference"])
    assert gaps.pop("change") < 1e-3, on_four_devices["reference"]
    assert max(gaps.values()) < 1e-5, gaps


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange", "control"])
def test_mesh_cell_broken_is_not_correct(on_four_devices, fault):
    """The step with half of each batch left out, the step with the exchange
    between chips left out, and the control (the spread reference with its
    products rounded to float8) put in the program's place."""
    assert on_four_devices[fault]["correct"] is False, on_four_devices[fault]


DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _program(compiled) -> str:
    """The compiled HLO without its debug information: the source files,
    lines and stack frames that the step was built from."""
    blocks = compiled.as_text().split("\n\n")
    text = "\n\n".join(b for b in blocks if b.split("\n", 1)[0] not in DEBUG_TABLES)
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("family", sorted(SMALL))
def test_one_chip_step_compiles_as_before(family):
    """The HLO of ``Cell``'s compiled step against that of the step built by
    the calls the one-chip harness has always made."""
    from repro.runtime import train_step as ts
    from repro.sharding.plan import make_plan

    cfg_doc, traffic, _ = SMALL[family]
    cell = jobs.Cell(cfg_doc, traffic, SEED, "")
    try:
        before = jax.jit(ts.build_train_step(cell.model, make_plan(cell.cfg, None), cell.opt),
                         donate_argnums=(0,))
        suite = jobs.ShapeSuite(traffic["name"], traffic.get("seq_len", 0), traffic["batch"],
                                "train")
        state = jax.eval_shape(lambda k: ts.init_train_state(cell.model, k, cell.opt),
                               jax.random.key(0))
        hlo = _program(before.lower(state, cell.model.input_specs(suite)).compile())
        assert _program(cell.compiled) == hlo
        assert cell.devices == jax.devices()[:1] and cell.batch_sharding is None
    finally:
        cell.close()


if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                    str(Path(__file__).resolve().parents[2] / "src")]
    print(json.dumps(_child()))
