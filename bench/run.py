"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` -> ``workloads``) names a configuration file,
a traffic file (``bench/traffic/<traffic>.json``) and the chips it needs.
Set-up builds the jobs, compiles their one step, and starts each job's
loop, which drives the job through its first three steps (the steps the
comparison reads) and waits. Then every loop runs on for ``--seconds``; with ``--trace 1`` that window is profiled. After
the window: the peak of device memory, then the plain reference (the
number compared beside each limit), then the metrics, each computed by its
reader ``bench/metrics/<metric>.py``. The last line of standard output is
one JSON object; without a TPU, or with fewer chips than the cell needs,
the run prints no result and exits 2.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))


class NoChip(RuntimeError):
    pass


def load_cell(name: str) -> dict:
    """The workload entry, its configuration, traffic and limits, and the
    metric entries it reports, from ``BENCHMARK.json`` and ``bench/``."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    reports = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return {
        "cell": cell,
        "cfg": json.loads((ROOT / config["file"]).read_text()),
        "traffic": json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if reports(m)],
        "per_layer": [m for m in spec["per_layer"] if reports(m)],
    }


def chips(need: int):
    """The local TPUs, or NoChip naming what was found instead."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found platform {devs[0].platform!r}")
    if len(devs) < need:
        raise NoChip(f"needs {need} chips, found {len(devs)}")
    return devs


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(c: dict, seed: int, seconds: float, trace: bool, *, t0: float = T0) -> dict:
    """Set-up, window, peak memory, comparison and metrics of one run."""
    import jax
    from jax.profiler import TraceAnnotation

    import compare
    import jobs
    import xplane

    cfg, traffic = c["cfg"], c["traffic"]
    flops = importlib.import_module(f"flops.{cfg['family']}")
    t_cell = time.perf_counter()
    cell = jobs.Cell(cfg, traffic, seed, compare.out_leaf(cfg))
    t_built = time.perf_counter()
    readings = cell.checked_steps()
    setup_s = time.perf_counter() - t0

    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(logdir)
    with TraceAnnotation(xplane.WINDOW):
        start, end = cell.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    peak_bytes = cell.peak_bytes()
    devices = cell.devices
    steps = sum(len(j.ends) for j in cell.jobs)
    run = {
        "cfg": cfg, "traffic": traffic, "unit": flops.UNIT,
        "setup_s": setup_s, "window_s": end - start, "peak_bytes": peak_bytes,
        "items": steps * flops.items_per_step(cfg, traffic),
        "model_flops": steps * flops.step_flops(cfg, traffic),
        "intervals_s": [b - a for j in cell.jobs for a, b in zip([start] + j.ends, j.ends)],
        "jobs": [{"steps": len(j.ends), "wait_s": j.wait_s, "batches": j.batches}
                 for j in cell.jobs],
        "chips": len(devices), "peak": peaks()[devices[0].device_kind],
        "trace": None,
    }
    failed = sum(j.failed for j in cell.jobs)
    cell.close()
    del cell

    if trace:
        tr = xplane.Trace.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        names = list(tr.devices)
        peak = run["peak"]
        run["trace"] = {
            "window_s": tr.window[1] - tr.window[0],
            "busy_s": statistics.fmean(tr.busy_s(d) for d in names),
            # (collective, exposed collective) seconds, averaged over the chips
            "collective_s": [statistics.fmean(x) for x in zip(*map(tr.collective_s, names))],
            "kernels": {
                k: {"seconds": statistics.fmean(tr.op_seconds(d, pat) for d in names),
                    "least_s": steps / run["chips"] * sum(
                        max(f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"])
                        for f, b in flops.kernel_calls(cfg, traffic, k))}
                for k, pat in flops.KERNELS.items()},
            "breakdown": {"device_ops": tr.top_ops(), "idle_gaps": tr.gaps_by_host_span()},
        }

    t_ref = time.perf_counter()
    correct, checks, job_gaps = compare.check(cfg, traffic, seed, readings, c["limits"],
                                              place=compare.placement(traffic, devices))
    print(f"bench: set-up {setup_s:.1f} s (imports {t_cell - t0:.1f}, step and state "
          f"{t_built - t_cell:.1f}, checked steps {t0 + setup_s - t_built:.1f}), window "
          f"{end - start:.1f} s, reference {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    correct = correct and failed == 0
    metrics = {}
    for m in c["per_layer" if trace else "end_to_end"]:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    result["job_gaps"] = job_gaps  # each job's numbers, with the leaf each is worst at
    result["checks"] = checks
    return result


def peaks() -> dict:
    return json.loads((BENCH / "peaks.json").read_text())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    c = load_cell(args.workload)
    try:
        devs = chips(c["cell"]["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if devs[0].device_kind not in peaks():
        print(f"bench: no peaks for device kind {devs[0].device_kind!r}", file=sys.stderr)
        return 2

    import jax
    from repro.launch.train import use_compile_cache

    use_compile_cache()
    # every program of the cell, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(c, args.seed, args.seconds, bool(args.trace))
    for name, chk in result["checks"].items():
        print(f"check {name} {chk['value']!r} limit {chk['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
