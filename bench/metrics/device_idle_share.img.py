"""Share of the traced window in which no operation ran on the device (1 -
union of the device's op intervals over the window), averaged over the
cell's chips, in percent; read in cells that train on images."""


def read(run):
    if run["unit"] != "images" or run["trace"] is None:
        return None
    return 100.0 * (1.0 - run["trace"]["busy_s"] / run["trace"]["window_s"])
