"""Compile each one-chip cell's train step, and the plain reference's loss
and gradients, for a described TPU v5e, at the cell's own sizes.

Nothing runs. The TPU compiler refuses what the chip would refuse and
``memory_analysis`` tells whether the program fits one chip's HBM. The
topology is described inside a fixture (one process at a time may load the
TPU library). Run with ``-s`` to see the sizes:

    PYTHONPATH=src:bench JAX_PLATFORMS=cpu python -m pytest -s bench/tests/test_rehearsal.py
"""
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import compare
import jobs
from repro.models import attention
from repro.runtime import train_step as ts
from repro.sharding.plan import make_plan

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
HBM = 16 * 2**30
ONE_CHIP = [w for w in SPEC["workloads"] if w["chips"] == 1]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


def _cell(w):
    cfg = json.loads((BENCH.parent / {c["name"]: c for c in SPEC["configs"]}[w["config"]]["file"]).read_text())
    return cfg, json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())


def _live(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _put(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


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
