"""Images of every step of every job completed in the window, over the
window (host clock)."""


def read(run):
    if run["unit"] != "images":
        return None
    return run["items"] / run["window_s"]
