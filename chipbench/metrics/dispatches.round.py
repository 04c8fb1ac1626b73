"""L-sized kernel dispatches per decoded round: the delta of the engine's
``engine.dispatches`` counter (``CodingEngine.dispatch_count``)."""
LAYER = "engine.CodingEngine"
UNIT = "dispatches"
MOVES = "round_s"


def read(run):
    rounds = run.obs.get("rounds")
    return run.obs["dispatches"] / rounds if rounds else None
