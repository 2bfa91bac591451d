"""One step of the training loop on the host, with a span and a counter per phase.

A step is five calls, in this order and each a phase of its own:

  ``pipeline.get``  take the next batch from the input pipeline
  ``device_put``    hand it to the runtime for the device
  ``dispatch``      call the compiled step, until the call returns
  ``sync``          ``block_until_ready`` on its result
  ``loss``          fetch the scalars the loop reads (``float``)

``HostLoop.step`` writes each phase as a ``jax.profiler.TraceAnnotation``
with ``job`` and ``step`` as arguments, the five as siblings inside a
``StepTraceAnnotation("train", step_num=n)``. They cost next to nothing
unless a profiler session records; then they land in the session's trace on
the clock of the device's ops, so each idle gap of the device can be named
by the phase the host was in. The counters are always on: host seconds and
calls per phase, read with ``time.perf_counter`` at the same boundaries.

The step is compiled ahead (``lower().compile()``), and a ``Compiled`` cannot
recompile, so no phase hides a compile.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Sequence, Tuple

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

PHASES = ("pipeline.get", "device_put", "dispatch", "sync", "loss")


class HostLoop:
    """The per-step calls of one training job, timed and annotated per phase.

    ``compiled`` maps (state, batch) to (state, metrics); ``pipeline`` has
    ``get()``. ``fetch`` names the metrics each step reads back to the host,
    ``sharding`` is where ``device_put`` places the batch (None: the default
    device), ``job`` names the job in the spans, and ``start_step`` numbers
    the first step.
    """

    def __init__(self, compiled, pipeline, *, job: str = "train",
                 fetch: Sequence[str] = ("loss",), sharding=None, start_step: int = 0):
        self.compiled, self.pipeline = compiled, pipeline
        self.job, self.fetch, self.sharding = job, tuple(fetch), sharding
        self.next_step = start_step
        self._seconds = dict.fromkeys(PHASES, 0.0)
        self._calls = dict.fromkeys(PHASES, 0)

    def _done(self, phase: str, t0: float) -> float:
        t = time.perf_counter()
        self._seconds[phase] += t - t0
        self._calls[phase] += 1
        return t

    def step(self, state) -> Tuple[Any, Dict[str, float]]:
        """One step from ``state``; returns the new state and the fetched
        metrics as floats."""
        n = self.next_step
        with StepTraceAnnotation("train", step_num=n):
            t = time.perf_counter()
            with TraceAnnotation("pipeline.get", job=self.job, step=n):
                batch = self.pipeline.get()
            t = self._done("pipeline.get", t)
            with TraceAnnotation("device_put", job=self.job, step=n):
                batch = jax.device_put(batch, self.sharding)
            t = self._done("device_put", t)
            with TraceAnnotation("dispatch", job=self.job, step=n):
                out = self.compiled(state, batch)
            t = self._done("dispatch", t)
            with TraceAnnotation("sync", job=self.job, step=n):
                state, metrics = jax.block_until_ready(out)
            t = self._done("sync", t)
            with TraceAnnotation("loss", job=self.job, step=n):
                values = {k: float(metrics[k]) for k in self.fetch}
            self._done("loss", t)
        self.next_step = n + 1
        return state, values

    def stats(self) -> Dict[str, float]:
        """Steps completed, and each phase's host seconds and calls so far
        (``<phase>_s``, ``<phase>_calls``), as ``HostPipeline.stats()``
        gives its input wait."""
        out = {"steps": float(self._calls["loss"])}
        for p in PHASES:
            out[f"{p}_s"] = self._seconds[p]
            out[f"{p}_calls"] = float(self._calls[p])
        return out


def phase_ms(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, float]:
    """Host milliseconds per call of each phase between two ``stats()``
    readings (0 for a phase with no call in between)."""
    out = {}
    for p in PHASES:
        calls = after[f"{p}_calls"] - before[f"{p}_calls"]
        out[p] = 1e3 * (after[f"{p}_s"] - before[f"{p}_s"]) / calls if calls else 0.0
    return out
