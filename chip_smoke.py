"""Smoke test on the chip: train through ``repro.launch.train`` on a TPU.

    python chip_smoke.py              # phases 1-3 on one chip
    python chip_smoke.py --chips 4    # phase 4 only, on a 2x2 host

Phases (one process: a chip belongs to the process that first touches it):

1. ``resnet_medium`` (ResNet50-V2 on ImageNet64 shapes, batch 32), the
   paper's workload, for 8 steps at full size.
2. ``granite-3-2b`` at its published widths with depth cut from 40 to 8
   layers, seq 2048, batch 4. The compiled step must hold the Pallas flash
   kernels (``tpu_custom_call``), and the kernel is checked against
   ``kernels/ref.mha_reference`` at the granite attention shape.
   Then ``granite-4-h-micro``, its first period (10 layers: Mamba-2 and one
   attention layer without positions) at published widths, seq 2048,
   batch 2; its step must hold the flash kernels too.
3. Two ``resnet_small`` jobs interleaved from two threads on the one chip
   (the paper's naive sharing); each job's losses must equal its solo run.
4. ``--chips 4``: phase 2's model on a 2x2 (data, model) mesh against the
   same steps on one chip.

Weights and data are random, made from fixed seeds. Every printed time is a
smoke reading, not a benchmark. The last line of standard output is the
JSON object ``{"ok": true, "device": {...}}``; any failed check raises, so no
phase can fail while the script exits 0. Without a TPU it exits 1 and names
the platform it found.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops, ref  # noqa: E402
from repro.launch.train import build_argparser, run, use_compile_cache  # noqa: E402

PHASE1 = ["--arch", "resnet_medium", "--batch", "32", "--steps", "8"]
PHASE2 = ["--arch", "granite-3-2b", "--layers", "8", "--seq", "2048",
          "--batch", "4", "--steps", "8"]
PHASE2_HYBRID = ["--arch", "granite-4-h-micro", "--layers", "10", "--seq", "2048",
                 "--batch", "2", "--steps", "6"]
PHASE3 = ["--arch", "resnet_small", "--batch", "32", "--steps", "6"]
# granite's attention: 32 query heads over 8 KV heads, head_dim 64
GRANITE_ATTN = dict(B=1, S=2048, H=32, KVH=8, D=64)

# bf16 keeps 8 mantissa bits (relative rounding 2**-8 ~ 4e-3 per element);
# the kernel and the f32 oracle round at different points, so outputs are
# held to the repo's bf16 kernel tolerance, and gradients, which sum 2048
# such terms, to 2e-2 of their largest magnitude
FWD_TOL = 2e-2
GRAD_REL_TOL = 2e-2
# sharded vs one-device losses: bf16 reductions in another order (the
# tolerance tests/test_multidevice.py holds the same comparison to)
MESH_LOSS_TOL = 3e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def train(name: str, argv: list[str]) -> dict:
    """One run of the normal training entry point; checks finite losses."""
    r = run(build_argparser().parse_args(argv + ["--log-every", "1000"]))
    losses = r["losses"]
    check(len(losses) == r["steps"] and all(math.isfinite(x) for x in losses),
          f"{name}: non-finite or missing losses {losses}")
    print(f"[{name}] {' '.join(argv)}", flush=True)
    print(f"[{name}] compile_s={r['compile_s']:.3f} "
          f"mean_step_ms={r['mean_step_ms']} "
          f"first_loss={losses[0]:.6f} last_loss={losses[-1]:.6f} "
          f"peak_bytes_in_use={r['peak_bytes_in_use']} "
          f"tpu_custom_calls={r['tpu_custom_calls']}", flush=True)
    return r


def flash_kernel_vs_ref(mode: str = "tpu", *, B, S, H, KVH, D) -> dict:
    """Pallas flash attention (forward and gradients) against the oracle."""
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KVH, D), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KVH, D), jnp.float32).astype(jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * ct)

    kernel = lambda q, k, v: ops.flash_attention(q, k, v, mode=mode)
    with jax.default_matmul_precision("highest"):
        o_ref = ref.mha_reference(q, k, v)
        g_ref = jax.jit(jax.grad(loss(ref.mha_reference), (0, 1, 2)))(q, k, v)
    o = jax.jit(kernel)(q, k, v)
    g = jax.jit(jax.grad(loss(kernel), (0, 1, 2)))(q, k, v)

    f32 = lambda x: np.asarray(x, np.float32)
    fwd_err = float(np.max(np.abs(f32(o) - f32(o_ref))))
    np.testing.assert_allclose(f32(o), f32(o_ref), atol=FWD_TOL, rtol=FWD_TOL)
    grad_rel = {}
    for name, a, b in zip(("dq", "dk", "dv"), g, g_ref):
        grad_rel[name] = float(np.max(np.abs(f32(a) - f32(b))) / np.max(np.abs(f32(b))))
        check(grad_rel[name] <= GRAD_REL_TOL,
              f"flash {name}: max error {grad_rel[name]:.3e} of max |ref| "
              f"> {GRAD_REL_TOL}")
    out = {"shape": dict(B=B, S=S, H=H, KVH=KVH, D=D), "fwd_max_abs_err": fwd_err,
           "fwd_tol": FWD_TOL, "grad_max_rel_err": grad_rel, "grad_tol": GRAD_REL_TOL}
    print(f"[flash-vs-ref] {json.dumps(out)}", flush=True)
    return out


def interleaved(argv: list[str], seeds=(1, 2)) -> dict:
    """Jobs solo, then the same jobs from concurrent threads on one device."""
    jobs = {s: argv + ["--seed", str(s)] for s in seeds}
    solo = {s: train(f"solo seed={s}", a)["losses"] for s, a in jobs.items()}
    with ThreadPoolExecutor(len(jobs)) as pool:
        futs = {s: pool.submit(train, f"interleaved seed={s}", a) for s, a in jobs.items()}
        together = {s: f.result()["losses"] for s, f in futs.items()}
    for s in seeds:
        check(together[s] == solo[s],
              f"seed {s}: interleaved losses {together[s]} != solo {solo[s]}")
    print(f"[interleaved] {len(seeds)} jobs match their solo loss traces exactly",
          flush=True)
    return {"solo": solo, "interleaved": together}


def mesh_vs_one_chip(argv: list[str]) -> dict:
    """The same steps on one device and on a (data, model) mesh of all."""
    one = train("one chip", argv)["losses"]
    host = train("2x2 mesh", argv + ["--mesh", "host"])
    diffs = [abs(a - b) for a, b in zip(one, host["losses"])]
    check(max(diffs) <= MESH_LOSS_TOL,
          f"mesh losses {host['losses']} differ from one chip {one} "
          f"by up to {max(diffs):.3e} > {MESH_LOSS_TOL}")
    print(f"[mesh-vs-one] max |loss diff| {max(diffs):.3e} <= {MESH_LOSS_TOL}",
          flush=True)
    return {"one_chip": one, "mesh": host["losses"], "max_abs_diff": max(diffs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    n = len(jax.devices())
    if n < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {n}", file=sys.stderr)
        return 1
    print(f"[device] {dev.device_kind} x{n}; compile cache {use_compile_cache()}",
          flush=True)

    if args.chips == 4:
        mesh_vs_one_chip(PHASE2)
    else:
        train("phase1 resnet_medium", PHASE1)
        r = train("phase2 granite-3-2b", PHASE2)
        # forward + dq + dk/dv kernels per layer scan body
        check(r["tpu_custom_calls"] >= 3,
              f"phase2: {r['tpu_custom_calls']} tpu_custom_call ops in the step")
        flash_kernel_vs_ref(**GRANITE_ATTN)
        r = train("phase2 granite-4-h-micro", PHASE2_HYBRID)
        check(r["tpu_custom_calls"] >= 3,
              f"phase2: {r['tpu_custom_calls']} tpu_custom_call ops in the hybrid's step")
        interleaved(PHASE3)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
