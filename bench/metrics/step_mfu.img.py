"""Model FLOPs of every step completed in the traced window (bench/flops,
nothing recomputed counted) over window x chips x bf16 peak, in percent;
read in cells that train on images."""


def read(run):
    if run["unit"] != "images" or run["trace"] is None:
        return None
    peak = run["peak"]["bf16_flops_per_s"]
    return 100.0 * run["model_flops"] / (run["window_s"] * run["chips"] * peak)
