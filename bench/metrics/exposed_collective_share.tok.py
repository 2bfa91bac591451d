"""Share of the traced window in which a collective op (all-gather,
reduce-scatter, all-reduce, collective-permute, all-to-all, their async
halves and the fusions that hold one) runs on a chip and no other op does,
averaged over the chips, in percent; read in cells that train on tokens over
a mesh. A mesh cell's window that shows no collective op is an error: the
names in ``xplane.COLLECTIVES`` no longer match the trace."""


def read(run):
    if run["unit"] != "tokens" or run["trace"] is None or "mesh" not in run["traffic"]:
        return None
    collective_s, exposed_s = run["trace"]["collective_s"]
    if collective_s <= 0:
        raise RuntimeError("the step runs over a mesh, but no op of the trace "
                           "matches xplane.COLLECTIVES")
    return 100.0 * exposed_s / run["trace"]["window_s"]
