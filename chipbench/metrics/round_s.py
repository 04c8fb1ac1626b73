"""Seconds per decoded round: the window's seconds over the rounds decoded
in it; undecodable attempts stay in the window."""
LAYER = None
UNIT = "s"
MOVES = None


def read(run):
    rounds = run.obs.get("rounds")
    return run.obs["window_s"] / rounds if rounds else None
