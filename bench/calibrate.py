"""Readings that the limits in ``limits/<workload>.json`` are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... [--control 1,2,3]

For each ``--seeds`` seed, in one process: a whole run of the cell (set-up,
its jobs' three checked steps on their loop threads, a window of
``--seconds``, the reference and the comparison under the cell's limits):
the lower readings. For each ``--control`` seed, job 0 of the cell with
the program's readings replaced, each put through the same comparison
under the cell's limits: the reference with its products rounded to
float8 (the control), and the reference fed half of each batch (the fault
"half the batch left out"): the upper readings; for the first of them also
the reference at a learning rate of 0, whose weights never move (the fault
"a step that returns its state unchanged"). The reference runs where the
cell's own does: on one chip, or laid over the cell's chips. One JSON line
per reading on standard output, with ``correct`` and each job's numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def _half(batches):
    return [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in batches]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    try:
        devices = run.chips(c["cell"]["chips"])[: c["cell"]["chips"]]
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    from repro.launch.train import use_compile_cache

    use_compile_cache()
    import compare

    cfg, traffic, limits = c["cfg"], c["traffic"], c["limits"]
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        r = run.run_cell(c, seed, args.seconds, False, t0=time.perf_counter())
        print(json.dumps({"kind": "program", "seed": seed, "correct": r["correct"],
                          "checks": r["checks"], "jobs": r["job_gaps"],
                          "metrics": r["metrics"], "peak": r["device"]["memory_peak_bytes"]}), flush=True)
    place = compare.placement(traffic, devices)
    still = dict(traffic, optimizer=dict(traffic["optimizer"], lr_peak=0.0, lr_min=0.0))
    for i, seed in enumerate(int(s) for s in args.control.split(",") if s):
        ref = compare.reference(cfg, traffic, seed, 0, place=place)
        kinds = [("control_fp8", traffic, {"lowp": True}),
                 ("fault_half_batch", traffic, {"rows": _half})]
        if i == 0:
            kinds.append(("fault_state_unchanged", still, {}))
        for kind, t, kw in kinds:
            reading = compare.reference(cfg, t, seed, 0, place=place, **kw)
            ok, checks, jobs = compare.check(cfg, traffic, seed, [reading], limits, refs=[ref])
            print(json.dumps({"kind": kind, "seed": seed, "correct": ok, "checks": checks,
                              "jobs": jobs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
