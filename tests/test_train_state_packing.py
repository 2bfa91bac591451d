"""Train state stacked by shape: the same steps as the per-leaf tree, the
model's own views, and when the stacking engages."""
import argparse
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint.store import CheckpointStore
from repro.configs.base import ShapeSuite
from repro.configs.registry import get_config
from repro.data import synthetic
from repro.launch.mesh import make_mesh_shape
from repro.launch.train import run
from repro.models.model_api import build_model
from repro.optim import adamw
from repro.runtime import train_step as ts
from repro.sharding.plan import make_plan, param_pspecs, validate_pspecs, zero_param_pspecs

# two blocks a stage, so blocks of one stage repeat their shapes
CFG = dataclasses.replace(get_config("resnet_medium"), img_size=16, n_classes=10,
                          stages=(2, 2), base_width=8)
SUITE = ShapeSuite("t", 0, 8, "train")
OPT = adamw.AdamWConfig(warmup_steps=1, total_steps=10)


def _model():
    return build_model(CFG)


def _per_leaf(state):
    """The per-leaf tree of a stacked state, with the same values."""
    return {"params": state["params"], "opt": state["opt"]}


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _paths(tree):
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_three_steps_match_the_per_leaf_tree(grad_accum):
    model = _model()
    stacked = ts.init_train_state(model, jax.random.key(0), OPT)
    assert isinstance(stacked, ts.StackedState)
    plain = _per_leaf(stacked)
    step = jax.jit(ts.build_train_step(model, make_plan(CFG, None), OPT, grad_accum=grad_accum))
    for i in range(3):
        batch = synthetic.batch_for(CFG, SUITE, seed=0, step=i)
        stacked, ms = step(stacked, batch)
        plain, mp = step(plain, batch)
        assert isinstance(stacked, ts.StackedState) and isinstance(plain, dict)
        for k in ("loss", "grad_norm"):
            np.testing.assert_allclose(float(ms[k]), float(mp[k]), rtol=1e-6)
    # Biases start at zero and stand near 1e-3 after three steps; the
    # gradients' sums, run in another order, move them by up to about 2e-9.
    for a, b in zip(_leaves(stacked["params"]), _leaves(plain["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)


def test_views_take_the_paths_and_values_of_model_init():
    model = _model()
    key = jax.random.key(4)
    state = ts.init_train_state(model, key, OPT)
    params = model.init(key)
    assert set(state) == {"params", "opt"}
    assert _paths(state["params"]) == _paths(params)
    for a, b in zip(_leaves(state["params"]), _leaves(params)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    opt = state["opt"]
    assert isinstance(opt, adamw.AdamWState) and int(opt.step) == 0
    assert _paths(opt.m) == _paths(params) == _paths(opt.v)
    # the views of an abstract state are abstract
    shapes = jax.eval_shape(lambda k: ts.init_train_state(model, k, OPT), key)
    assert [(s.shape, s.dtype) for s in jax.tree_util.tree_leaves(shapes["params"])] == \
        [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(params)]


def test_moment_views_are_exact_slices_of_the_stacked_arrays():
    model = _model()
    state = ts.init_train_state(model, jax.random.key(0), OPT)
    step = jax.jit(ts.build_train_step(model, make_plan(CFG, None), OPT))
    state, _ = step(state, synthetic.batch_for(CFG, SUITE, seed=0, step=0))
    views = state["opt"]
    for stored, view in ((state.m, views.m), (state.v, views.v), (state.params, state["params"])):
        leaves = _leaves(view)
        for g, x in zip(state.layout.members, stored):
            x = np.asarray(x)
            parts = [x] if len(g) == 1 else list(x)
            for i, part in zip(g, parts):
                np.testing.assert_array_equal(leaves[i], part)
    assert any(np.asarray(x).any() for x in state.m)


def _shapes(cfg):
    model = build_model(cfg)
    return model, jax.eval_shape(lambda k: ts.init_train_state(model, k, OPT),
                                 jax.random.key(0))


def test_resnet50_v2_goes_from_457_to_169_arrays():
    model, state = _shapes(get_config("resnet_medium"))
    assert isinstance(state, ts.StackedState)
    assert ts.state_arrays(state) == {"before": 457, "after": 169}
    assert len(state.layout.members) == 56
    # what is stacked, and so copied by a view, is under 1% of the bytes
    nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
    stacked = sum(nbytes(x) for g, x in zip(state.layout.members, state.params) if len(g) > 1)
    assert stacked < 0.01 * sum(nbytes(x) for x in state.params)


def test_views_pass_on_the_leaves_stored_alone():
    model = _model()
    state = ts.init_train_state(model, jax.random.key(0), OPT)
    alone = {g[0]: x for g, x in zip(state.layout.members, state.params) if len(g) == 1}
    assert alone
    leaves = jax.tree_util.tree_leaves(state["params"])
    assert all(leaves[i] is x for i, x in alone.items())


def test_granite_l12_keeps_its_34_arrays_and_its_step():
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=12)
    model, state = _shapes(cfg)
    assert type(state) is dict
    assert ts.state_arrays(state) == {"before": 34, "after": 34}
    suite = ShapeSuite("t", 4096, 2, "train")
    step = jax.jit(ts.build_train_step(model, make_plan(cfg, None), OPT), donate_argnums=(0,))
    lowered = step.lower(state, model.input_specs(suite))
    assert len(jax.tree_util.tree_leaves(lowered.args_info[0][0])) == 34
    # and a sharded step's shardings stay per leaf
    st_sh = ts.state_shardings(model, make_mesh_shape((1, 1), ("data", "model")))
    assert type(st_sh) is dict and len(jax.tree_util.tree_leaves(st_sh)) == 34


def test_a_sharded_step_places_each_stacked_array_as_its_leaves():
    mesh = make_mesh_shape((1, 1), ("data", "model"))
    model = build_model(CFG)
    state = ts.init_train_state(model, jax.random.key(0), OPT)
    jitted, st_sh, _, _ = ts.jit_train_step(model, mesh, SUITE, OPT)
    # the shardings take the state's own layout, so the pair needs no mesh
    # at initialisation
    assert jax.tree_util.tree_structure(st_sh) == jax.tree_util.tree_structure(state)
    batch = synthetic.batch_for(CFG, SUITE, seed=0, step=0)
    _, mp = jax.jit(ts.build_train_step(model, make_plan(CFG, None), OPT))(state, batch)
    sharded, ms = jitted(jax.device_put(ts.init_train_state(model, jax.random.key(0), OPT),
                                        st_sh), batch)
    assert isinstance(sharded, ts.StackedState)
    np.testing.assert_allclose(float(ms["loss"]), float(mp["loss"]), rtol=1e-6)


def test_stacked_arrays_keep_their_leaves_specs_on_every_variant():
    model = build_model(get_config("resnet_medium").reduced())
    params = jax.eval_shape(model.init, jax.random.key(0))
    layout = ts.StackLayout.of(params)
    mesh = make_mesh_shape((1, 1), ("data", "model"))
    for variant in ("baseline", "zero"):
        st_sh = ts.state_shardings(model, mesh, variant)
        specs = jax.tree_util.tree_leaves(
            validate_pspecs(params, param_pspecs(params), mesh) if variant == "baseline"
            else zero_param_pspecs(params, mesh), is_leaf=lambda s: isinstance(s, P))
        for g, sh in zip(layout.members, st_sh.params):
            assert len({specs[i] for i in g}) == 1
            want = specs[g[0]] if len(g) == 1 else P(None, *specs[g[0]])
            assert sh.spec == want


def test_checkpoint_round_trips_a_stacked_state(tmp_path):
    model = _model()
    state = ts.init_train_state(model, jax.random.key(0), OPT)
    step = jax.jit(ts.build_train_step(model, make_plan(CFG, None), OPT))
    state, _ = step(state, synthetic.batch_for(CFG, SUITE, seed=0, step=0))
    store = CheckpointStore(tmp_path)
    store.save(1, state)
    like = ts.init_train_state(model, jax.random.key(9), OPT)
    back, _ = store.restore(like, 1)
    assert isinstance(back, ts.StackedState) and back.layout == state.layout
    for a, b in zip(_leaves(back), _leaves(state)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    batch = synthetic.batch_for(CFG, SUITE, seed=0, step=1)
    np.testing.assert_array_equal(float(step(back, batch)[1]["loss"]),
                                  float(step(state, batch)[1]["loss"]))


def _per_leaf_init(model, key):
    params = model.init(key)
    return {"params": params, "opt": adamw.init_state(params, OPT)}


def test_checkpoints_are_per_leaf_whatever_the_layout(tmp_path):
    """A stacked state writes the files of the per-leaf state, so either
    layout restores from the other's checkpoint."""
    model = _model()
    stacked = ts.init_train_state(model, jax.random.key(0), OPT)
    step = jax.jit(ts.build_train_step(model, make_plan(CFG, None), OPT))
    stacked, _ = step(stacked, synthetic.batch_for(CFG, SUITE, seed=0, step=0))
    per_leaf = _per_leaf(stacked)
    CheckpointStore(tmp_path / "stacked").save(1, stacked)
    CheckpointStore(tmp_path / "per_leaf").save(1, per_leaf)
    manifests = [json.loads((tmp_path / d / "step_00000001" / "manifest.json").read_text())
                 for d in ("stacked", "per_leaf")]
    assert [m["key"] for m in manifests[0]["leaves"]] == \
        [m["key"] for m in manifests[1]["leaves"]]
    # the stacked state's checkpoint into the per-leaf tree of an earlier run
    back, _ = CheckpointStore(tmp_path / "stacked").restore(
        _per_leaf_init(model, jax.random.key(9)))
    assert type(back) is dict
    for a, b in zip(_leaves(back), _leaves(per_leaf)):
        np.testing.assert_array_equal(a, b)
    # a per-leaf checkpoint into a stacked state
    back, _ = CheckpointStore(tmp_path / "per_leaf").restore(
        ts.init_train_state(model, jax.random.key(9), OPT))
    assert isinstance(back, ts.StackedState) and back.layout == stacked.layout
    for a, b in zip(_leaves(back), _leaves(stacked)):
        np.testing.assert_array_equal(a, b)


def test_a_checkpoint_restores_onto_a_mesh(tmp_path):
    """The elastic path: restore with a mesh's shardings, then step there."""
    model = _model()
    state = ts.init_train_state(model, jax.random.key(0), OPT)
    store = CheckpointStore(tmp_path)
    store.save(1, state)
    mesh = make_mesh_shape((1, 1), ("data", "model"))
    jitted, st_sh, b_sh, _ = ts.jit_train_step(model, mesh, SUITE, OPT)
    back, _ = store.restore(ts.init_train_state(model, jax.random.key(9), OPT),
                            shardings=st_sh)
    assert all(x.sharding == s for x, s in zip(jax.tree_util.tree_leaves(back),
                                               jax.tree_util.tree_leaves(st_sh)))
    batch = synthetic.batch_for(CFG, SUITE, seed=0, step=0)
    _, mp = jax.jit(ts.build_train_step(model, make_plan(CFG, None), OPT))(state, batch)
    _, ms = jitted(back, jax.device_put(batch, b_sh))
    np.testing.assert_allclose(float(ms["loss"]), float(mp["loss"]), rtol=1e-6)


def _args(**overrides):
    base = dict(
        arch="resnet_medium", reduced=True, steps=4, batch=2, seq=0,
        grad_accum=1, lr=1e-3, warmup=2, seed=0, workers=1, max_queue_size=2,
        ckpt_dir="", ckpt_every=2, log_every=2, mesh="none", metrics_out="",
        layers=0, total_steps=6,
    )
    base.update(overrides)
    return argparse.Namespace(**base)


def test_launcher_reports_state_arrays_and_resumes_a_stacked_state(tmp_path, capsys):
    r = run(_args(ckpt_dir=str(tmp_path)))
    assert r["state_arrays"] == {"before": 241, "after": 85}
    assert "[train] state arrays 241 -> 85" in capsys.readouterr().out
    resumed = run(_args(ckpt_dir=str(tmp_path), steps=6))
    assert resumed["steps"] == 2 and np.isfinite(resumed["final_loss"])


def test_launcher_resumes_an_unsharded_checkpoint_on_a_host_mesh(tmp_path):
    """``--mesh none`` then ``--mesh host`` over two devices, from one
    checkpoint directory (placeholder CPU devices, so in a subprocess)."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src") + \
        os.pathsep + env.get("PYTHONPATH", "")
    code = textwrap.dedent(f"""
        import argparse, json
        from repro.launch.train import run
        base = dict(arch="resnet_medium", reduced=True, batch=2, seq=0, grad_accum=1,
                    lr=1e-3, warmup=2, seed=0, workers=1, max_queue_size=2,
                    ckpt_dir={str(tmp_path)!r}, ckpt_every=2, log_every=2,
                    metrics_out="", layers=0, total_steps=6)
        a = run(argparse.Namespace(**base, steps=2, mesh="none"))
        b = run(argparse.Namespace(**base, steps=4, mesh="host"))
        print(json.dumps({{"a": a["state_arrays"], "b": b["state_arrays"],
                          "devices": b["device"]["count"], "steps": b["steps"],
                          "loss": b["final_loss"]}}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=560, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["devices"] == 2 and r["steps"] == 2 and np.isfinite(r["loss"])
    assert r["a"] == r["b"] == {"before": 241, "after": 85}
