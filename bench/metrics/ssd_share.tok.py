"""Device time of the SSD scan (the ops ``flops/<family>.py:KERNELS['ssd']``
matches, averaged over the cell's chips) over the traced window, in
percent: the scan's share of the step. Nothing to read where the step makes
no SSD call; an error where it makes them and no op matches, or XLA loops
other than the family's count of them (``flops/<family>.py:check_ssd_loops``)."""
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
    return 100.0 * k["seconds"] / run["trace"]["window_s"]
