"""The trace reduction on hand-made intervals (union, subtraction, busy
time, kernel time by name and idle gaps named by host span, all inside the
window), and on a trace recorded on the chip."""
import gzip
import re
from pathlib import Path

import pytest

from flops import dense
from xplane import Trace, is_collective, leaves, measure, subtract, union


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
    assert recorded.collective_s(d) == (0.0, 0.0)  # one chip: no op is a collective


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


# Ops of the granite-3-2b-l40.mesh2x2 step as the TPU compiler emits them for
# a 2x2 v5e mesh (bench/tests/test_rehearsal.py), shortened: the collectives,
# their async halves and a fusion that is one; and not collectives, a matmul
# that gathers its next operand inside it (async_collective_fusion) and ops
# that merely take a collective's result as an operand.
HLO_OPS = {
    "%fusion.490 = (f32[1,4096,4,64]{3,1,2,0:T(8,128)S(1)}, bf16[4,64,1024]{2,1,0:T(8,128)(2,1)}) "
    "fusion(%get-tuple-element.1526, %all-gather.316, %bitcast.867), kind=kOutput, "
    "calls=%async_collective_fusion.490": False,
    "%async-collective-start.1 = (bf16[16,64,1024]{2,1,0:T(8,128)(2,1)S(1)}, "
    "bf16[16,64,2048]{2,1,0:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}) fusion(%bitcast.828), "
    "kind=kCustom, output_to_operand_aliasing={{0}: (0, {})}, calls=%fused_computation.405": True,
    "%async-collective-done.1 = bf16[16,64,2048]{2,1,0:T(8,128)(2,1)S(1)} "
    "fusion(%get-tuple-element.1556, %get-tuple-element.1557), kind=kCustom, "
    "calls=%fused_computation.407": True,
    "%fusion.461 = bf16[4096,1024]{1,0:T(8,128)(2,1)S(1)} fusion(%custom-call.83), "
    "kind=kCustom, calls=%all-reduce-scatter.4.clone.clone": True,
    "%all-reduce.79 = bf16[1,4096,2048]{2,1,0:T(8,128)(2,1)S(1)} "
    "all-reduce(%get-tuple-element.1595), channel_id=22, replica_groups=[2,2]<=[4], "
    "use_global_device_ids=true, to_apply=%add.2.clone": True,
    "%collective-permute-start = (bf16[40,4096]{1,0:T(8,128)(2,1)S(1)}, "
    "bf16[40,4096]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
    "collective-permute-start(%slice.113), channel_id=130, source_target_pairs={{0,2},{1,3}}": True,
    "%collective-permute-done = bf16[40,4096]{1,0:T(8,128)(2,1)S(1)} "
    "collective-permute-done(%collective-permute-start)": True,
    "%all-to-all = bf16[2,1,4096,1024]{3,2,1,0:T(8,128)(2,1)S(1)} all-to-all(%all-reduce.24), "
    "channel_id=72, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}": True,
    "%all-gather.314 = bf16[24584,2048]{1,0:T(8,128)(2,1)} all-gather(%param.20), "
    "channel_id=73, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={1}": True,
    "%add.688 = bf16[1,4096,2048]{2,1,0:T(8,128)(2,1)S(1)} "
    "add(%get-tuple-element.1358, %all-reduce.84)": False,
    "%while.48 = (s32[]{:T(128)}, bf16[1,4096,2048]{2,1,0:T(8,128)(2,1)S(1)}) "
    "while(%tuple.12), condition=%wide.region_9.cond, body=%wide.region_8.18_spmd.sunk": False,
    "%flash_fwd.2 = (bf16[1,4,4096,4,64]{4,3,2,1,0:T(4,128)(2,1)S(1)}, "
    "f32[1,4,16384,128]{3,2,1,0:T(8,128)}) custom-call(%bitcast.834, "
    "%maximum_bitcast_fusion.4), custom_call_target=\"tpu_custom_call\"": False,
    "%fusion.12 = bf16[4096,2048]{1,0:T(8,128)(2,1)} fusion(%all-gather.314, %copy-done.3), "
    "kind=kOutput, calls=%fused_computation.12": False,
}


@pytest.mark.parametrize("op", list(HLO_OPS), ids=[o.split(" = ")[0] for o in HLO_OPS])
def test_collectives_by_name_opcode_or_called_computation(op):
    assert is_collective(op) is HLO_OPS[op]


AR, AG, MM = ("%all-reduce.1 = f32[8] all-reduce(%x)", "%all-gather.2 = f32[8] all-gather(%y)",
              "%fusion.3 = f32[8] fusion(%all-reduce.1), calls=%fused_computation.3")
START, DONE = ("%collective-permute-start = (f32[8], f32[8]) collective-permute-start(%z)",
               "%collective-permute-done = f32[8] collective-permute-done(%collective-permute-start)")


@pytest.mark.parametrize("ops, collective, exposed", [
    # an all-reduce wholly under a matmul's fusion: nothing exposed
    ([(1.0, 2.0, AR), (0.5, 3.0, MM)], 1.0, 0.0),
    # an all-gather half under compute: its other half exposed
    ([(0.0, 2.0, MM), (1.0, 3.0, AG)], 2.0, 1.0),
    # two collectives alone, overlapping each other: their union, all exposed
    ([(4.0, 6.0, AR), (5.0, 7.0, AG), (0.0, 1.0, MM)], 3.0, 3.0),
    # async halves: the start and the wait at the done are collective time;
    # compute between them hides the transfer and counts for neither
    ([(0.0, 0.1, START), (0.1, 2.0, MM), (2.0, 2.5, DONE)], 0.6, 0.6),
    # a loop spans the ops of its body: only they say what runs
    ([(0.0, 10.0, "%while.1 = f32[8] while(%t), body=%b"), (1.0, 2.0, MM), (2.0, 4.0, AR)], 2.0, 2.0),
])
def test_exposed_collective_time(ops, collective, exposed):
    t = Trace({"/device:TPU:0": sorted(ops)}, [], (0.0, 10.0))
    assert t.collective_s("/device:TPU:0") == pytest.approx((collective, exposed))


def test_leaves_drop_loops_branches_and_calls():
    ops = [(0, 10, "%while.1 = f32[8] while(%t), body=%b"), (1, 2, "%fusion.2 = f32[8] fusion(%x)"),
           (3, 4, "%call.3 = f32[8] call(%x), to_apply=%f"),
           (5, 6, "%conditional.4 = f32[8] conditional(%p, %x, %y)"),
           (7, 8, "%copy-start.5 = (f32[8], f32[8]) copy-start(%x)")]
    assert [n.split(" = ")[0] for _, _, n in leaves(ops)] == ["%fusion.2", "%copy-start.5"]
