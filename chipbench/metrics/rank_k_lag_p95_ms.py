"""95th percentile, over every generation whose rank-k packet was due in
the window, of the end of the tick that completed it minus that packet's
due time, in ms.  A generation that never completed counts as infinite."""
import numpy as np

LAYER = None
UNIT = "ms"
MOVES = None


def read(run):
    lag = run.obs.get("lag_s")
    if lag is None or lag.size == 0:
        return None
    p95 = float(np.quantile(lag, 0.95, method="higher"))
    return 1e3 * p95 if np.isfinite(p95) else None
