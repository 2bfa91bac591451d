"""Compile each cell's train step, and the plain reference's loss and
gradients, for a described TPU v5e (one chip, or the four of a 2x2 host for
a cell on a mesh, the reference laid over them as ``refs/common.py:spread``
lays it), at the cell's own sizes.

Nothing runs. The TPU compiler refuses what the chip would refuse and
``memory_analysis`` tells whether the program fits each chip's HBM. The
topology is described inside a fixture (one process at a time may load the
TPU library). Run with ``-s`` to see the sizes:

    PYTHONPATH=src:bench JAX_PLATFORMS=cpu python -m pytest -s bench/tests/test_rehearsal.py
"""
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

import compare
import jobs
import xplane
from refs import common
from repro.models import attention
from repro.runtime import train_step as ts
from repro.sharding.plan import make_plan

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
HBM = 16 * 2**30
ONE_CHIP = [w for w in SPEC["workloads"] if w["chips"] == 1]
MESH = [w for w in SPEC["workloads"] if w["chips"] > 1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
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


def _cell(w):
    cfg = json.loads((BENCH.parent / {c["name"]: c for c in SPEC["configs"]}[w["config"]]["file"]).read_text())
    return cfg, json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())


def _live(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _put(sharding, tree, place=None):
    """Shapes of ``tree`` on ``sharding``, or each as ``place`` lays it."""
    return jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding or place(s.shape)), tree)


@pytest.mark.parametrize("w", ONE_CHIP, ids=[w["name"] for w in ONE_CHIP])
def test_step_and_reference_fit_one_chip(one_chip, w, monkeypatch):
    # the kernel dispatch asks the process's backend; take the chip's path
    monkeypatch.setattr(attention, "_kernel_mode", lambda: "tpu")
    cfg_doc, traffic = _cell(w)
    cfg = jobs.model_config(cfg_doc)
    model = jobs.build_model(cfg)
    opt = jobs.opt_config(traffic)
    suite = jobs.ShapeSuite("t", traffic.get("seq_len", 0), traffic["batch"], "train")
    state = jax.eval_shape(lambda k: ts.init_train_state(model, k, opt), jax.random.key(0))
    step = jax.jit(ts.build_train_step(model, make_plan(cfg, None), opt), donate_argnums=(0,))
    compiled = step.lower(_put(one_chip, state), _put(one_chip, model.input_specs(suite))).compile()
    live = _live(compiled) * traffic["jobs"]  # naive sharing: every job's state, one step at a time
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')

    init, loss = compare._fns(cfg_doc["family"], json.dumps(cfg_doc, sort_keys=True), False)
    params = jax.eval_shape(init, jax.random.key(0))
    batch = jax.tree_util.tree_map(jnp.asarray, compare.gen.batch(cfg_doc, traffic, 0, 0))
    ref = compare.common._loss_and_grads.lower(
        _put(one_chip, params), _put(one_chip, batch), loss=loss).compile()
    print(f"\n[rehearsal] {w['name']}: step {_live(compiled) / 2**30:.3f} GiB "
          f"({compiled.memory_analysis()}), x{traffic['jobs']} jobs {live / 2**30:.3f} GiB, "
          f"tpu_custom_call {kernels}; reference loss+grads {_live(ref) / 2**30:.3f} GiB")
    assert live < HBM and _live(ref) < HBM


@pytest.mark.parametrize("w", MESH, ids=[w["name"] for w in MESH])
def test_mesh_step_and_spread_reference_fit_each_chip(topo, w, monkeypatch):
    """The program's sharded step as ``jobs.Cell`` builds it, and the
    reference laid over the same chips; each chip's share must fit."""
    monkeypatch.setattr(attention, "_kernel_mode", lambda: "tpu")
    cfg_doc, traffic = _cell(w)
    cfg = jobs.model_config(cfg_doc)
    model = jobs.build_model(cfg)
    opt = jobs.opt_config(traffic)
    suite = jobs.ShapeSuite("t", traffic["seq_len"], traffic["batch"], "train")
    mesh = Mesh(np.asarray(topo.devices).reshape(traffic["mesh"]["shape"]),
                tuple(traffic["mesh"]["axes"]),
                axis_types=(AxisType.Auto,) * len(traffic["mesh"]["axes"]))
    jitted, state_sh, batch_sh, _ = ts.jit_train_step(model, mesh, suite, opt)
    state = jax.eval_shape(lambda k: ts.init_train_state(model, k, opt), jax.random.key(0))
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), state, state_sh)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_sh[k])
             for k, v in model.input_specs(suite).items()}
    compiled = jitted.lower(state, batch).compile()
    hlo = compiled.as_text()
    collectives = {c: len(re.findall(rf" {c}(-start)?\(", hlo)) for c in xplane.COLLECTIVES}
    kernels = hlo.count('custom_call_target="tpu_custom_call"')

    place = common.spread(topo.devices)
    init, loss = compare._fns(cfg_doc["family"], json.dumps(cfg_doc, sort_keys=True), False)
    params = jax.eval_shape(init, jax.random.key(0))
    tokens = compare.gen.batch(cfg_doc, traffic, 0, 0)
    ref = common.loss_and_grads(place, params).lower(
        _put(None, params, place), _put(place((1,)), tokens), loss=loss).compile()
    # the reference's AdamW moments stay on the chips beside it, in float32
    moments = sum(8 * x.size for x in jax.tree_util.tree_leaves(params)) // len(topo.devices)
    print(f"\n[rehearsal] {w['name']} per chip: step {_live(compiled) / 2**30:.3f} GiB "
          f"({compiled.memory_analysis()}), collectives {collectives}, "
          f"tpu_custom_call {kernels}; reference loss+grads {_live(ref) / 2**30:.3f} GiB "
          f"({ref.memory_analysis()}) and moments {moments / 2**30:.3f} GiB")
    assert sum(collectives.values()) > 0 and kernels > 0
    assert _live(compiled) < HBM and _live(ref) + moments < HBM
