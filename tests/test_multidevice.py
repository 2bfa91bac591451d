"""Multi-device semantics, run in subprocesses with placeholder CPU devices
(XLA_FLAGS must be set before jax initializes, so these cannot run in the
main pytest process)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_sub(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """The same train step on a 2x4 mesh and on one device must produce
    numerically close losses and parameters (GSPMD is semantics-preserving)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeSuite
        from repro.models.model_api import build_model
        from repro.optim import adamw
        from repro.runtime import train_step as ts
        from repro.sharding.plan import make_plan
        from repro.data import synthetic
        from repro.launch.mesh import make_mesh_shape

        cfg = get_config("granite-3-2b").reduced()
        suite = ShapeSuite("t", 32, 8, "train")
        model = build_model(cfg)
        opt = adamw.AdamWConfig(warmup_steps=1, total_steps=10)
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic.batch_for(cfg, suite, seed=0).items()}

        # single-device reference
        plan0 = make_plan(cfg, None)
        step0 = jax.jit(ts.build_train_step(model, plan0, opt))
        st0 = ts.init_train_state(model, jax.random.key(0), opt)
        st0, m0 = step0(st0, batch)
        st0, m0b = step0(st0, batch)

        # sharded
        mesh = make_mesh_shape((2, 4), ("data", "model"))
        jitted, st_sh, b_sh, plan = ts.jit_train_step(model, mesh, suite, opt)
        st = ts.init_train_state(model, jax.random.key(0), opt)
        st = jax.device_put(st, st_sh)
        b = jax.device_put(batch, b_sh)
        st, m1 = jitted(st, b)
        st, m1b = jitted(st, b)

        print(json.dumps({
            "loss0": float(m0["loss"]), "loss1": float(m1["loss"]),
            "loss0b": float(m0b["loss"]), "loss1b": float(m1b["loss"]),
        }))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert abs(r["loss0"] - r["loss1"]) < 3e-2, r
    assert abs(r["loss0b"] - r["loss1b"]) < 3e-2, r


def test_partitioner_carves_disjoint_contiguous_instances():
    out = run_sub("""
        import jax, json
        from repro.core.partitioner import device_grid, partition_homogeneous, verify_disjoint
        grid = device_grid(rows=8)  # 8x1 grid, 1 row per slice unit
        insts = partition_homogeneous(grid, "2g.10gb")
        verify_disjoint(insts)
        ids = [[int(d.id) for d in i.mesh.devices.flat] for i in insts]
        print(json.dumps(ids))
    """)
    ids = json.loads(out.strip().splitlines()[-1])
    assert len(ids) == 3  # 3x 2g.10gb
    flat = [d for grp in ids for d in grp]
    assert len(flat) == len(set(flat))
    for grp in ids:
        assert grp == sorted(grp) and grp[-1] - grp[0] == len(grp) - 1, (
            "instance not a contiguous block"
        )


def test_collectives_stay_inside_instance():
    """V2 isolation: a job compiled on one instance emits no collective that
    addresses devices outside the instance."""
    out = run_sub("""
        import jax, jax.numpy as jnp, json
        from repro.core.partitioner import device_grid, partition_homogeneous
        from repro.core.interference import check_collective_containment
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeSuite
        from repro.models.model_api import build_model
        from repro.optim import adamw
        from repro.runtime import train_step as ts

        grid = device_grid(rows=8)
        insts = partition_homogeneous(grid, "2g.10gb")
        inst = insts[1]  # middle instance: devices 2,3
        cfg = get_config("granite-3-2b").reduced()
        suite = ShapeSuite("t", 32, 4, "train")
        model = build_model(cfg)
        jitted, st_sh, b_sh, plan = ts.jit_train_step(
            model, inst.mesh, suite, adamw.AdamWConfig())
        state_shape = jax.eval_shape(
            lambda k: ts.init_train_state(model, k, adamw.AdamWConfig()),
            jax.random.key(0))
        lowered = jitted.lower(state_shape, model.input_specs(suite))
        hlo = lowered.compile().as_text()
        ok, why = check_collective_containment(
            hlo, [d.id for d in inst.mesh.devices.flat], inst.n_chips)
        print(json.dumps({"ok": ok, "why": why}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["ok"], r["why"]


def test_live_collocated_training_no_interference():
    """Two models really training in parallel on disjoint 4-device instances
    produce exactly the same losses as the same jobs run alone (F3, live)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, json, threading
        from repro.core.partitioner import device_grid, partition
        from repro.core.profiles import Placement
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeSuite
        from repro.models.model_api import build_model
        from repro.optim import adamw
        from repro.runtime import train_step as ts
        from repro.data import synthetic

        grid = device_grid(rows=8)
        insts = partition(grid, [Placement("3g.20gb", 0), Placement("3g.20gb", 4)])
        cfg = get_config("granite-3-2b").reduced()
        suite = ShapeSuite("t", 32, 4, "train")
        opt = adamw.AdamWConfig(warmup_steps=1, total_steps=20)

        def run_job(inst, seed, steps, out):
            model = build_model(cfg)
            jitted, st_sh, b_sh, plan = ts.jit_train_step(model, inst.mesh, suite, opt)
            st = jax.device_put(ts.init_train_state(model, jax.random.key(seed), opt), st_sh)
            losses = []
            for i in range(steps):
                batch = {k: jnp.asarray(v) for k, v in
                         synthetic.batch_for(cfg, suite, seed=seed, step=i).items()}
                batch = jax.device_put(batch, b_sh)
                st, m = jitted(st, batch)
                losses.append(float(m["loss"]))
            out[seed] = losses

        solo = {}
        run_job(insts[0], 1, 4, solo)
        run_job(insts[1], 2, 4, solo)

        par = {}
        t1 = threading.Thread(target=run_job, args=(insts[0], 1, 4, par))
        t2 = threading.Thread(target=run_job, args=(insts[1], 2, 4, par))
        t1.start(); t2.start(); t1.join(); t2.join()
        print(json.dumps({"solo": solo, "par": par}))
    """, devices=8)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["solo"]["1"] == r["par"]["1"], "job 1 diverged under collocation"
    assert r["solo"]["2"] == r["par"]["2"], "job 2 diverged under collocation"
