"""Share of the window in which no operation ran on the device, percent."""
LAYER = "device"
UNIT = "%"
MOVES = "round_s"


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
