"""Least time of every flash-attention kernel call in the traced window (the
larger of its FLOPs over the bf16 peak and its bytes over HBM bandwidth, from
bench/flops) over the summed device time of those kernels' ops, in percent.
Nothing to read where the step makes no such call. A trace that shows none
of the kernels' ops although the step makes the calls is an error: the ops'
names in ``flops/<family>.py:KERNELS`` no longer match the trace."""


def read(run):
    k = (run["trace"] or {}).get("kernels", {}).get("flash")
    if not k or k["least_s"] <= 0:
        return None
    if k["seconds"] <= 0:
        raise RuntimeError("the step makes flash-attention calls, but no op of the "
                           "trace matches flops/dense.py:KERNELS['flash']")
    return 100.0 * k["least_s"] / k["seconds"]
