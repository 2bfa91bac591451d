"""Tokens of every step completed in the window, over the window (host clock)."""


def read(run):
    if run["unit"] != "tokens":
        return None
    return run["items"] / run["window_s"]
