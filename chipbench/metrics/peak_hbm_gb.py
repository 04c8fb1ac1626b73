"""Peak device memory after the window, in GB (1e9 bytes): the largest
``peak_bytes_in_use`` over the cell's chips."""
LAYER = None
UNIT = "GB"
MOVES = None


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
