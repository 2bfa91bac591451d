"""Sharding-variant semantics: zero/sp/serve must be numerically equivalent
to baseline (they change WHERE tensors live, never WHAT is computed)."""
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


def test_all_variants_match_baseline_loss():
    out = run_sub("""
        import jax, jax.numpy as jnp, json
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeSuite
        from repro.models.model_api import build_model
        from repro.optim import adamw
        from repro.runtime import train_step as ts
        from repro.data import synthetic
        from repro.launch.mesh import make_mesh_shape

        cfg = get_config("granite-3-2b").reduced()
        suite = ShapeSuite("t", 32, 8, "train")
        model = build_model(cfg)
        opt = adamw.AdamWConfig(warmup_steps=1, total_steps=10)
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic.batch_for(cfg, suite, seed=0).items()}
        mesh = make_mesh_shape((2, 4), ("data", "model"))
        losses = {}
        for variant in ("baseline", "sp", "zero"):
            jitted, st_sh, b_sh, plan = ts.jit_train_step(
                model, mesh, suite, opt, variant=variant)
            st = jax.device_put(ts.init_train_state(model, jax.random.key(0), opt), st_sh)
            b = jax.device_put(batch, b_sh)
            st, m = jitted(st, b)
            st, m2 = jitted(st, b)
            losses[variant] = [float(m["loss"]), float(m2["loss"])]
        print(json.dumps(losses))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    for variant in ("sp", "zero"):
        for a, b in zip(r["baseline"], r[variant]):
            assert abs(a - b) < 3e-2, (variant, r)


def test_serve_variant_decode_matches_baseline():
    out = run_sub("""
        import jax, jax.numpy as jnp, json, numpy as np
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeSuite
        from repro.models.model_api import build_model
        from repro.runtime import serve_step as serve
        from repro.sharding.plan import make_plan
        from repro.runtime.serve_step import pad_cache
        from repro.launch.mesh import make_mesh_shape

        cfg = get_config("granite-3-2b").reduced()
        model = build_model(cfg)
        mesh = make_mesh_shape((2, 4), ("data", "model"))
        suite = ShapeSuite("d", 32, 8, "decode")
        params = model.init(jax.random.key(0))
        plan0 = make_plan(cfg, None)
        B, S = 8, 31
        toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab, jnp.int32)
        last, cache = model.prefill(params, {"tokens": toks}, plan0)
        cache = pad_cache(cache, 1)
        tok = jnp.argmax(last, -1).astype(jnp.int32)
        ref, _ = model.decode(params, {"token": tok}, cache, S, plan0)

        outs = {}
        for variant in ("baseline", "serve"):
            jitted, p_sh, tok_sh, c_sh, plan = serve.jit_decode_step(
                model, mesh, suite, variant=variant)
            p = jax.device_put(params, p_sh)
            c = jax.device_put(cache, c_sh)
            t = jax.device_put({"token": tok}, tok_sh)
            logits, _ = jitted(p, t, c)
            outs[variant] = np.asarray(logits, np.float32)
        err_b = float(np.max(np.abs(outs["baseline"] - np.asarray(ref, np.float32))))
        err_s = float(np.max(np.abs(outs["serve"] - np.asarray(ref, np.float32))))
        print(json.dumps({"baseline": err_b, "serve": err_s}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["baseline"] < 6e-2, r
    assert r["serve"] < 6e-2, r
