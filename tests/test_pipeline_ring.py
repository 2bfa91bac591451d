"""GPipe pipeline stage + ring collective-matmul: validated against their
single-device / all-gather oracles on 8 placeholder devices (subprocess),
plus the edge shapes the gang comms model prices (core/gang/comms.py):
world_size 1 (a 1-ring is a no-op — zero links, zero overhead) and an odd
stage count (a 3-ring closes, so every stage boundary is a priced link)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

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


def test_ring_matmuls_match_oracles():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh_shape
        from repro.runtime.ring import ring_ag_matmul, ring_rs_matmul

        mesh = make_mesh_shape((4,), ("m",))
        B, d, f = 8, 16, 32  # f_local = f // 4
        x = jax.random.normal(jax.random.key(0), (B, d))
        w = jax.random.normal(jax.random.key(1), (d, f))

        def ag(xl, wl):
            return ring_ag_matmul(xl, wl, "m")

        y = jax.shard_map(ag, mesh=mesh, in_specs=(P("m", None), P(None, "m")),
                      out_specs=P("m", None), check_vma=False)(x, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=2e-5, atol=1e-5)

        # reduce flavour: x (B, f) sharded on batch, w (f, d) row-sharded
        x2 = jax.random.normal(jax.random.key(2), (B, f))
        w2 = jax.random.normal(jax.random.key(3), (f, d))

        def rs(xl, wl):
            return ring_rs_matmul(xl, wl, "m")

        y2 = jax.shard_map(rs, mesh=mesh, in_specs=(P("m", None), P("m", None)),
                       out_specs=P("m", None), check_vma=False)(x2, w2)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(x2 @ w2),
                                   rtol=2e-5, atol=1e-5)
        print(json.dumps({"ok": True}))
    """)
    assert json.loads(out.strip().splitlines()[-1])["ok"]


def test_gpipe_pipeline_matches_plain_forward():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.configs.registry import get_config
        from repro.models.model_api import build_model
        from repro.models import transformer as tfm
        from repro.launch.mesh import make_mesh_shape
        from repro.runtime.pipeline import pipeline_forward
        from repro.sharding.plan import make_plan

        cfg = get_config("granite-3-2b").reduced(n_layers=4)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        plan = make_plan(cfg, None)
        M, mb, S = 3, 2, 16  # 3 microbatches of 2 sequences
        toks = jax.random.randint(jax.random.key(1), (M, mb, S), 0, cfg.vocab, jnp.int32)

        ref = tfm.forward(cfg, params, toks.reshape(M * mb, S), plan)
        mesh = make_mesh_shape((4,), ("stage",))
        got = pipeline_forward(cfg, params, toks, mesh)
        got = got.reshape(M * mb, S, -1)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))))
        print(json.dumps({"err": err}))
    """, devices=4)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["err"] < 6e-2, r


def test_ring_matmuls_world_size_one_degenerate():
    """A 1-wide ring (gang world_size 1): one scan step, the ppermute is a
    self-loop, and both flavours reduce to a plain local matmul — the
    runtime-side mirror of comm_overhead_s() == 0 for a degree-1 axis."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh_shape
        from repro.runtime.ring import ring_ag_matmul, ring_rs_matmul

        mesh = make_mesh_shape((1,), ("m",))
        B, d, f = 4, 8, 16
        x = jax.random.normal(jax.random.key(0), (B, d))
        w = jax.random.normal(jax.random.key(1), (d, f))
        y = jax.shard_map(lambda xl, wl: ring_ag_matmul(xl, wl, "m"), mesh=mesh,
                      in_specs=(P("m", None), P(None, "m")),
                      out_specs=P("m", None), check_vma=False)(x, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=2e-5, atol=1e-5)
        x2 = jax.random.normal(jax.random.key(2), (B, f))
        w2 = jax.random.normal(jax.random.key(3), (f, d))
        y2 = jax.shard_map(lambda xl, wl: ring_rs_matmul(xl, wl, "m"), mesh=mesh,
                       in_specs=(P("m", None), P("m", None)),
                       out_specs=P("m", None), check_vma=False)(x2, w2)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(x2 @ w2),
                                   rtol=2e-5, atol=1e-5)
        print(json.dumps({"ok": True}))
    """, devices=1)
    assert json.loads(out.strip().splitlines()[-1])["ok"]


def test_gpipe_odd_stage_count_matches_plain_forward():
    """Three pipeline stages (odd ring — the wrap link is real, unlike the
    even 2-stage chain) over a 3-layer reduction: the GPipe schedule still
    reproduces the plain scanned forward."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.configs.registry import get_config
        from repro.models.model_api import build_model
        from repro.models import transformer as tfm
        from repro.launch.mesh import make_mesh_shape
        from repro.runtime.pipeline import pipeline_forward
        from repro.sharding.plan import make_plan

        cfg = get_config("granite-3-2b").reduced(n_layers=3)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        plan = make_plan(cfg, None)
        M, mb, S = 4, 2, 16
        toks = jax.random.randint(jax.random.key(1), (M, mb, S), 0, cfg.vocab, jnp.int32)

        ref = tfm.forward(cfg, params, toks.reshape(M * mb, S), plan)
        mesh = make_mesh_shape((3,), ("stage",))
        got = pipeline_forward(cfg, params, toks, mesh)
        got = got.reshape(M * mb, S, -1)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))))
        print(json.dumps({"err": err}))
    """, devices=3)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["err"] < 6e-2, r


def test_edge_shapes_feed_matching_comms_bandwidth_terms():
    """The scheduling-side mirror of the two edge shapes above: the comms
    model prices a world_size-1 axis at exactly zero and a 3-stage
    pipeline ring over its three closed-ring links with (d-1)/d traffic
    scaling — the bandwidth terms the gang step time charges."""
    from repro.core.gang.comms import (
        AXIS_TRAFFIC, DEFAULT_LINK, comm_overhead_s, ring_links,
    )
    from repro.core.gang.parallelism import Parallelism, axis_rank_groups

    # world_size 1: no groups, no links, no overhead (matches the 1-ring)
    assert axis_rank_groups(Parallelism()) == {}
    assert ring_links([0]) == ()
    assert comm_overhead_s(Parallelism(), {0: "d0"}, 1e-3) == 0.0

    # odd pipeline: 3 stages close a ring — 3 links, 2/3 of the ring
    # all-reduce bytes, weighted by the pipeline axis traffic share
    pp3 = Parallelism(pipeline=3)
    (group,) = axis_rank_groups(pp3)["pipeline"]
    assert len(ring_links(group)) == 3
    colocated = comm_overhead_s(pp3, {0: "d0", 1: "d0", 2: "d0"}, 1e-3)
    assert colocated == pytest.approx(AXIS_TRAFFIC["pipeline"] * 1e-3 * (2 / 3))
    # scattering the odd ring prices every link at the cross rate + latency
    scattered = comm_overhead_s(pp3, {0: "d0", 1: "d1", 2: "d2"}, 1e-3)
    assert scattered == pytest.approx(
        colocated / DEFAULT_LINK.cross_bandwidth_frac
        + 3 * DEFAULT_LINK.cross_latency_s
    )
