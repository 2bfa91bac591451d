"""The training loop's step on the host: one span per phase in the profiler's
trace, always-on counters per phase, and the launcher's use of both."""
import argparse
import glob
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.launch.train import run
from repro.runtime.host_loop import PHASES, HostLoop, phase_ms


class Batches:
    """A pipeline stand-in: ``get()`` hands out numbered batches."""

    def __init__(self):
        self.n = 0

    def get(self):
        self.n += 1
        return {"x": np.full((4,), self.n, np.float32)}


def _step(state, batch):
    w = state["w"] + batch["x"].mean()
    return {"w": w}, {"loss": jnp.sum(w), "grad_norm": jnp.abs(w).max()}


@pytest.fixture(scope="module")
def compiled():
    state = {"w": jnp.zeros((4,), jnp.float32)}
    batch = {"x": jax.ShapeDtypeStruct((4,), jnp.float32)}
    return jax.jit(_step).lower(state, batch).compile()


def _state():
    return {"w": jnp.zeros((4,), jnp.float32)}


def _host_events(logdir):
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    names = set(PHASES) | {"train"}
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                        for e in line.events if e.name in names]
    return sorted(out)


def test_step_writes_five_sibling_spans_per_step_under_a_train_marker(compiled, tmp_path):
    loop = HostLoop(compiled, Batches(), job="job1", start_step=7)
    state = _state()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            state, _ = loop.step(state)
    finally:
        jax.profiler.stop_trace()

    events = _host_events(tmp_path)
    markers = [e for e in events if e[2] == "train"]
    assert [m[3]["step_num"] for m in markers] == [7, 8, 9]
    for s0, s1, _, _ in markers:
        spans = [e for e in events if e[2] != "train" and s0 <= e[0] and e[1] <= s1]
        assert [e[2] for e in spans] == list(PHASES)
        # siblings: each phase ends before the next one starts
        assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
    spans = [e for e in events if e[2] != "train"]
    assert len(spans) == 3 * len(PHASES)
    assert {(e[3]["job"], e[3]["step"]) for e in spans} == {("job1", n) for n in (7, 8, 9)}


def test_counters_count_steps_and_stay_within_the_wall_time(compiled):
    loop = HostLoop(compiled, Batches(), fetch=("loss", "grad_norm"))
    state = _state()
    t0 = time.perf_counter()
    for _ in range(5):
        state, fetched = loop.step(state)
    wall = time.perf_counter() - t0
    s = loop.stats()
    assert s["steps"] == 5
    assert all(s[f"{p}_calls"] == 5 for p in PHASES)
    assert all(s[f"{p}_s"] >= 0 for p in PHASES)
    assert sum(s[f"{p}_s"] for p in PHASES) <= wall
    # batches 1..5 each add their value to w, so w = 15 in each of 4 lanes
    assert fetched == {"loss": 60.0, "grad_norm": 15.0}
    assert loop.next_step == 5


def test_phase_ms_is_per_call_between_two_readings(compiled):
    loop = HostLoop(compiled, Batches())
    state, _ = loop.step(_state())
    a = loop.stats()
    for _ in range(4):
        state, _ = loop.step(state)
    b = loop.stats()
    ms = phase_ms(a, b)
    assert list(ms) == list(PHASES)
    for p in PHASES:
        assert ms[p] == pytest.approx(1e3 * (b[f"{p}_s"] - a[f"{p}_s"]) / 4)
    assert phase_ms(b, b) == dict.fromkeys(PHASES, 0.0)


def test_a_failed_dispatch_counts_only_the_phases_that_completed():
    def broken(state, batch):
        raise RuntimeError("device lost")

    loop = HostLoop(broken, Batches())
    with pytest.raises(RuntimeError, match="device lost"):
        loop.step(_state())
    s = loop.stats()
    assert (s["pipeline.get_calls"], s["device_put_calls"]) == (1, 1)
    assert (s["dispatch_calls"], s["sync_calls"], s["loss_calls"], s["steps"]) == (0, 0, 0, 0)
    assert loop.next_step == 0


def _args(**overrides):
    base = dict(
        arch="granite-3-2b", reduced=True, steps=6, batch=2, seq=16,
        grad_accum=1, lr=1e-3, warmup=2, seed=0, workers=1, max_queue_size=2,
        ckpt_dir="", ckpt_every=50, log_every=3, mesh="none", metrics_out="",
        layers=1, total_steps=6,
    )
    base.update(overrides)
    return argparse.Namespace(**base)


def test_launcher_reports_the_loop_counters_and_logs_each_phase(capsys):
    r = run(_args())
    loop = r["loop"]
    assert loop["steps"] == 6 and all(loop[f"{p}_calls"] == 6 for p in PHASES)
    # the mean of steps 4 to 6 of dispatch + sync lies within their totals
    assert 0 < r["mean_step_ms"] <= 1e3 * (loop["dispatch_s"] + loop["sync_s"])
    logs = [l for l in capsys.readouterr().out.splitlines() if l.startswith("[train] step")]
    assert len(logs) == 2
    assert all(f" {p}=" in l for l in logs for p in PHASES)


def test_launcher_gives_no_mean_step_time_within_the_first_three_steps():
    r = run(_args(steps=3, total_steps=3))
    assert r["mean_step_ms"] is None and r["loop"]["steps"] == 3
