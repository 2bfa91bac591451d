"""95th percentile, over every step of every job completed in the window, of
the time from that job's previous step completion (or the window's start)
to this one: what a job feels, stalls and input waits included."""

import statistics


def read(run):
    if len(run["intervals_s"]) < 20:
        return None
    return 1e3 * statistics.quantiles(run["intervals_s"], n=20)[-1]
