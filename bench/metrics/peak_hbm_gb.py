"""Peak device memory of the process, read from the runtime's allocator
(``memory_stats()["peak_bytes_in_use"]``) after the window and before the
reference runs, the largest over the cell's chips; in 1e9 bytes."""


def read(run):
    return run["peak_bytes"] / 1e9
