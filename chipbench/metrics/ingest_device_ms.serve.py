"""Device-busy milliseconds per server tick inside the window: the
DecoderBank's ingest scan and the eager slot updates around it."""
LAYER = "engine.DecoderBank"
UNIT = "ms"
MOVES = "rank_k_lag_p95_ms"


def read(run):
    ticks = run.obs.get("ticks")
    if run.trace is None or not ticks or run.trace.busy_s <= 0:
        return None
    return 1e3 * run.trace.busy_s / ticks
