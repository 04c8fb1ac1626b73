"""Set-up seconds: process start to the window's start (imports, inputs
from the seed, warm-up and any compilation or cache loads)."""
LAYER = None
UNIT = "s"
MOVES = None


def read(run):
    return run.setup_s
