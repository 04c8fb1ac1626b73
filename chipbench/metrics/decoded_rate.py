"""Decoded payload bytes (k·l per generation completed in the window) per
window second, in MB/s (1e6 bytes)."""
LAYER = None
UNIT = "MB/s"
MOVES = None


def read(run):
    b = run.obs.get("decoded_bytes")
    return b / run.obs["window_s"] / 1e6 if b else None
