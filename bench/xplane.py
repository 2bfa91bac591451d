"""Reduction of a JAX profiler trace to the numbers the per-layer readers use.

A trace (``.xplane.pb``) holds one plane per TPU (``/device:TPU:<n>``) whose
line ``XLA Ops`` has one event per operation the device ran, and host planes
whose threads carry the benchmark's own spans (``jax.profiler.TraceAnnotation``).
Host and device events share one clock in nanoseconds. Everything here is
read inside the window that the span ``WINDOW`` marks on the host.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from typing import Dict, List, Tuple

from jax.profiler import ProfileData

WINDOW = "bench_window"
# host spans of the benchmark's loop (jobs.Cell.step), to name idle gaps by
HOST_SPANS = ("pipeline.get", "device_put", "step", "loss")
DEVICE_LINE = "XLA Ops"
# XLA's collectives. An op is one where its own name or opcode holds one of
# these, or the TPU compiler's async-collective-start / -done, or where it
# calls a computation named for one (the fusion %all-reduce-scatter.N): the
# collectives, their async halves and the fusions that are a collective. Not
# one: an op that only takes a collective's result as an operand, and an
# async_collective_fusion, which is a matmul that runs the gather of its next
# operand inside it, hidden behind its own compute (its time is compute).
# An op's text is "%name = type opcode(operands), attributes".
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "collective-permute", "all-to-all")
_NAMED = re.compile("|".join(COLLECTIVES))
_ASYNC = re.compile("async-collective-(start|done)")
# the opcode: the first lower-case word before a parenthesis (a type's layout
# letters are upper case)
_OPCODE = re.compile(r"(?<![\w.%-])([a-z][a-z0-9-]*)\(")
_CALLS = re.compile(r"calls=%([\w.-]+)")
HOLDERS = ("while", "conditional", "call")  # ops that span the ops of a body


def is_collective(op: str) -> bool:
    name = op.partition(" = ")[0]
    calls = _CALLS.search(op.partition(" = ")[2])
    return (_NAMED.search(name) is not None or _ASYNC.search(name) is not None
            or _NAMED.match(opcode(op)) is not None
            or (calls is not None and _NAMED.match(calls.group(1)) is not None))


Interval = Tuple[float, float]  # seconds on the trace clock


class Trace:
    """Device ops per TPU, and host spans, as (start, end, name) in seconds."""

    def __init__(self, devices: Dict[str, list], host: list, window: Interval):
        self.devices, self.host, self.window = devices, host, window

    @classmethod
    def load(cls, logdir: str) -> "Trace":
        paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {logdir}, found {paths}")
        return cls.from_profile(ProfileData.from_file(paths[0]))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        devices, host, window = {}, [], None
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                       for line in plane.lines if line.name == DEVICE_LINE
                       for e in line.events]
                devices[plane.name] = sorted(ops)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == WINDOW:
                            window = (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        elif e.name in HOST_SPANS:
                            host.append((e.start_ns * 1e-9,
                                         (e.start_ns + e.duration_ns) * 1e-9, e.name))
        if window is None:
            raise RuntimeError(f"no {WINDOW!r} span on any host plane")
        return cls(devices, sorted(host), window)

    # -- per device ---------------------------------------------------------

    def ops(self, device: str, pattern: re.Pattern | None = None) -> List[tuple]:
        lo, hi = self.window
        return [(max(s, lo), min(e, hi), n) for s, e, n in self.devices[device]
                if e > lo and s < hi and (pattern is None or pattern.search(n))]

    def busy_s(self, device: str) -> float:
        return measure(union([(s, e) for s, e, _ in self.ops(device)]))

    def op_seconds(self, device: str, pattern: re.Pattern) -> float:
        """Summed durations of the ops whose name matches ``pattern``."""
        return sum(e - s for s, e, _ in self.ops(device, pattern))

    def collective_s(self, device: str) -> Tuple[float, float]:
        """Time in which a collective op runs, and the part of it in which
        no other op does (exposed). Loops, branches and calls are left out:
        only the ops they hold say what runs."""
        coll, other = [], []
        for s, e, n in leaves(self.ops(device)):
            (coll if is_collective(n) else other).append((s, e))
        coll = union(coll)
        return measure(coll), measure(subtract(coll, union(other)))

    def idle_gaps(self, device: str) -> List[Interval]:
        return subtract([self.window], union([(s, e) for s, e, _ in self.ops(device)]))

    # -- summaries ----------------------------------------------------------

    def top_ops(self, n: int = 10) -> List[list]:
        """Ops that took the most device time, seconds averaged over devices,
        by the op's name in its HLO text (a loop's op holds the ops of its
        body, which are listed too)."""
        total = defaultdict(float)
        for d in self.devices:
            for s, e, name in self.ops(d):
                total[name.split(" = ", 1)[0]] += (e - s) / len(self.devices)
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def gaps_by_host_span(self, n: int = 10) -> List[list]:
        """Device idle time, averaged over devices, named by the host span
        that overlaps each gap most (``none`` where no span does)."""
        starts = [s for s, _, _ in self.host]
        longest = max((e - s for s, e, _ in self.host), default=0.0)
        total = defaultdict(float)
        for d in self.devices:
            for gs, ge in self.idle_gaps(d):
                best, name = 0.0, "none"
                for i in range(bisect.bisect_left(starts, gs - longest), len(starts)):
                    s, e, span = self.host[i]
                    if s >= ge:
                        break
                    ov = min(e, ge) - max(s, gs)
                    if ov > best:
                        best, name = ov, span
                total[name] += (ge - gs) / len(self.devices)
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def opcode(op: str) -> str:
    m = _OPCODE.search(op.partition(" = ")[2])
    return m.group(1) if m else ""


def leaves(ops: List[tuple]) -> List[tuple]:
    """The (start, end, name) ops that are not a loop, a branch or a call:
    such an op spans the ops of its body, which the trace lists too."""
    return [o for o in ops if opcode(o[2]) not in HOLDERS]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` that no interval of the
    disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def measure(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)
