"""Time a step waited for its batch in ``HostPipeline.get`` (the program's
own input-wait counter), per step inside the window, averaged over jobs;
read in cells that train on images."""

import statistics


def read(run):
    if run["unit"] != "images" or run["trace"] is None:
        return None
    return statistics.fmean(1e3 * j["wait_s"] / j["batches"] for j in run["jobs"])
