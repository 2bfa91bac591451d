"""End-to-end training launcher.

The production entry point: build the model from ``--arch``, shard it over
the chosen mesh, stream deterministic synthetic data through the host
pipeline, checkpoint every ``--ckpt-every`` steps (async, atomic), resume
automatically from the latest valid checkpoint, and log the loss and the
host time of each phase of the loop step (``runtime/host_loop.py``). On a
CPU host use ``--reduced`` for a runnable config; on a TPU the same flags
drive the published widths, with ``--layers`` cutting depth to fit one chip.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

The step is compiled ahead of the loop, so ``compile_s`` in the result is
set-up time and ``mean_step_ms`` (steps after the first three, each timed
from the compiled call to its device sync) is steady state. ``loop`` holds
the per-phase counters of ``HostLoop.stats()``, ``state_arrays`` the
arrays the step takes as its state, per leaf (``before``) and as stored
(``after``: fewer when ``init_train_state`` stacks the leaves by shape).
Where the step takes the Pallas flash kernels, ``flash_tiles`` counts the
tiles of one (batch row, KV head) grid that they skip, mask and run
unmasked at ``--seq`` (``kernels/flash_attention.py:tile_classes``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from pathlib import Path

import numpy as np

import jax

from repro.configs.base import ShapeSuite
from repro.configs.registry import get_config
from repro.checkpoint.store import CheckpointStore
from repro.data import synthetic
from repro.data.pipeline import HostPipeline
from repro.kernels import flash_attention as fa
from repro.models.model_api import build_model
from repro.optim import adamw
from repro.launch.mesh import make_mesh_shape
from repro.runtime import train_step as ts
from repro.runtime.host_loop import HostLoop, phase_ms
from repro.sharding.plan import make_plan

# fixed, inside the checkout: the cache key includes the directory, so a
# per-process or temporary path would never hit
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself);
    otherwise the cache lives in ``.jax_cache/`` at the checkout root.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="CPU-scale config")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut depth to N layers at published widths (0: keep)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--total-steps", type=int, default=0,
                    help="LR schedule horizon (0 -> --steps); pin it when a "
                         "run will be interrupted/resumed so the schedule "
                         "is invariant to the stopping point")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--max-queue-size", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", choices=("none", "host"), default="none",
                    help="'host': mesh over all local devices (data x model)")
    ap.add_argument("--metrics-out", default="")
    return ap


def make_host_mesh():
    n = len(jax.devices())
    if n == 1:
        return None
    rows = max(1, n // 2)
    return make_mesh_shape((rows, n // rows), ("data", "model"))


def run(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        if cfg.family == "resnet":
            raise ValueError("--layers cuts stacked layers; resnet depth is its stages")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    suite = ShapeSuite("train_cli", args.seq, args.batch, "train")
    model = build_model(cfg)
    opt_cfg = adamw.AdamWConfig(
        lr_peak=args.lr, warmup_steps=args.warmup,
        total_steps=args.total_steps or max(args.steps, 1),
    )

    mesh = make_host_mesh() if args.mesh == "host" else None
    st_sh = b_sh = None
    if mesh is not None:
        jitted, st_sh, b_sh, plan = ts.jit_train_step(
            model, mesh, suite, opt_cfg, grad_accum=args.grad_accum
        )
    else:
        plan = make_plan(cfg, None)
        step_fn = ts.build_train_step(model, plan, opt_cfg, grad_accum=args.grad_accum)
        jitted = jax.jit(step_fn, donate_argnums=(0,))

    state = ts.init_train_state(model, jax.random.key(args.seed), opt_cfg)
    state_arrays = ts.state_arrays(state)
    print(f"[train] state arrays {state_arrays['before']} -> {state_arrays['after']}",
          flush=True)
    start_step = 0

    store = None
    if args.ckpt_dir:
        store = CheckpointStore(args.ckpt_dir)
        latest = store.latest_step()
        if latest is not None:
            state, extra = store.restore(state, latest)
            start_step = latest
            print(f"[train] resumed from step {latest}", flush=True)
    if st_sh is not None:
        state = jax.device_put(state, st_sh)

    t_compile0 = time.perf_counter()
    compiled = jitted.lower(state, model.input_specs(suite)).compile()
    compile_s = time.perf_counter() - t_compile0
    hlo = compiled.as_text()
    flash_tiles = None
    if "flash_fwd" in hlo:  # the step takes the Pallas flash kernels
        flash_tiles = fa.tile_classes(args.seq, args.seq, fa.BLOCK, fa.BLOCK, causal=True)
        print("[train] flash tiles " + " ".join(f"{k} {n}" for k, n in flash_tiles.items()),
              flush=True)

    pipeline = HostPipeline(
        lambda step: synthetic.batch_for(cfg, suite, seed=args.seed, step=step),
        workers=args.workers,
        max_queue_size=args.max_queue_size,
        start_step=start_step,
    ).start()

    loop = HostLoop(compiled, pipeline, job=args.arch, sharding=b_sh,
                    start_step=start_step)
    losses = []
    logged = warm = loop.stats()  # warm: after the first three steps
    t_train0 = time.perf_counter()
    try:
        for step in range(start_step, args.steps):
            state, fetched = loop.step(state)
            loss = fetched["loss"]
            losses.append(loss)
            if len(losses) == 3:
                warm = loop.stats()
            if not np.isfinite(loss):
                raise FloatingPointError(f"non-finite loss {loss} at step {step}")
            if (step + 1) % args.log_every == 0:
                now = loop.stats()
                phases = " ".join(f"{p}={ms:.2f}ms" for p, ms in phase_ms(logged, now).items())
                logged = now
                print(f"[train] step {step + 1}/{args.steps} loss={loss:.4f} {phases}",
                      flush=True)
            if store and (step + 1) % args.ckpt_every == 0:
                store.save(step + 1, state, extra={"loss": loss}, async_save=True)
    finally:
        pipeline.stop()
    if store:
        store.save(args.steps, state, extra={"loss": losses[-1]})
        store.wait()

    wall = time.perf_counter() - t_train0
    steady = phase_ms(warm, loop.stats())
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    result = {
        "arch": args.arch,
        "layers": cfg.n_layers,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "steps": args.steps - start_step,
        "losses": losses,
        "final_loss": losses[-1] if losses else None,
        "first_loss": losses[0] if losses else None,
        # window means: single-step losses on stochastic batches are too
        # noisy to compare individually
        "head_mean_loss": float(np.mean(losses[:5])) if losses else None,
        "tail_mean_loss": float(np.mean(losses[-5:])) if losses else None,
        "mean_step_ms": steady["dispatch"] + steady["sync"] if len(losses) > 3 else None,
        "compile_s": compile_s,
        # arrays the compiled step takes as its state, per leaf -> as stored
        "state_arrays": state_arrays,
        # Pallas kernels in the compiled step (0 on the XLA path)
        "tpu_custom_calls": hlo.count('custom_call_target="tpu_custom_call"'),
        # per (batch row, KV head): flash tiles skipped / masked / unmasked
        "flash_tiles": flash_tiles,
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "wall_s": wall,
        "pipeline": pipeline.stats(),
        "loop": loop.stats(),
    }
    if args.metrics_out:
        Path(args.metrics_out).write_text(json.dumps(result, indent=2))
    return result


def main():
    args = build_argparser().parse_args()
    use_compile_cache()
    result = run(args)
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
