"""The trace reduction on hand-made intervals (union, subtraction, busy
time, kernel time by name and idle gaps named by host span, all inside the
window), and on a trace recorded on the chip."""
import gzip
import re
from pathlib import Path

import pytest

from flops import dense
from xplane import Trace, measure, subtract, union


def test_union_merges_overlaps_and_drops_empty():
    assert union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]


def test_subtract_leaves_what_no_interval_covers():
    assert subtract([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == [(0, 1), (2, 3), (4, 9)]
    assert subtract([(0, 1), (5, 6)], []) == [(0, 1), (5, 6)]
    assert measure(subtract([(0, 1)], [(-1, 2)])) == 0


def _trace():
    dev = [(0.0, 1.0, "fusion.1"), (0.5, 2.0, "all-reduce.3"), (3.0, 4.0, "all-gather.1"),
           (3.5, 3.6, "convolution.2"), (6.0, 12.0, "fusion.1")]
    host = [(1.5, 3.2, "pipeline.get"), (4.0, 6.0, "step")]
    return Trace({"/device:TPU:0": dev}, host, (0.0, 10.0))


def test_busy_time_and_kernels_inside_the_window():
    t = _trace()
    d = "/device:TPU:0"
    # ops clipped to the window (0, 10): 0-2, 3-4, 6-10
    assert t.busy_s(d) == pytest.approx(7.0)
    assert t.op_seconds(d, re.compile(r"fusion")) == pytest.approx(5.0)
    assert t.idle_gaps(d) == [(2.0, 3.0), (4.0, 6.0)]
    assert dict(t.gaps_by_host_span()) == pytest.approx({"pipeline.get": 1.0, "step": 2.0})
    assert t.top_ops(1) == [["fusion.1", pytest.approx(5.0)]]



# A trace recorded on one TPU v5e chip: granite-3-2b-l12.train, a window of
# 3 s (6 steps) of bench/run.py --trace 1.
RECORDED = Path(__file__).parent / "data" / "granite_window.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return Trace.from_profile(ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes())))


def test_recorded_trace_busy_time_and_window(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    d = "/device:TPU:0"
    assert recorded.window[1] - recorded.window[0] == pytest.approx(3.568390164)
    assert recorded.busy_s(d) == pytest.approx(3.538628458)  # as the run reported it
    gaps = dict(recorded.gaps_by_host_span())
    assert sum(gaps.values()) == pytest.approx(3.568390164 - 3.538628458)


def test_recorded_trace_flash_kernels_by_name(recorded):
    """The four Pallas kernels per layer, and nothing that only takes one
    of their results as an operand."""
    ops = recorded.ops("/device:TPU:0", dense.KERNELS["flash"])
    assert len(ops) == 6 * 12 * 4
    assert {n.split(" = ")[0] for _, _, n in ops} == {
        "%closed_call.9", "%rematted_computation.10", "%checkpoint.20", "%checkpoint.21"}
    assert recorded.op_seconds("/device:TPU:0", dense.KERNELS["flash"]) == pytest.approx(
        1.371163636)
    top = recorded.top_ops(3)
    assert [n for n, _ in top] == ["%while.8", "%while.9", "%checkpoint.20"]
