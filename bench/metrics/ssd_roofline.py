"""Least time of every SSD scan call in the traced window (the larger of its
FLOPs over the bf16 peak and its bytes over HBM bandwidth, from bench/flops)
over the summed device time of the ops ``flops/<family>.py:KERNELS['ssd']``
matches, in percent. Nothing to read where the step makes no such call. A
trace that shows none of those ops although the step makes the calls, or
XLA loops other than the family's count of them
(``flops/<family>.py:check_ssd_loops``), is an error: the pattern no longer
matches the trace."""
import importlib


def read(run):
    k = (run["trace"] or {}).get("kernels", {}).get("ssd")
    if not k or k["least_s"] <= 0:
        return None
    if k["seconds"] <= 0:
        raise RuntimeError("the step makes SSD scan calls, but no op of the "
                           "trace matches flops/<family>.py:KERNELS['ssd']")
    flops = importlib.import_module(f"flops.{run['cfg']['family']}")
    flops.check_ssd_loops(run["cfg"], flops.KERNELS["ssd"].names)
    return 100.0 * k["least_s"] / k["seconds"]
