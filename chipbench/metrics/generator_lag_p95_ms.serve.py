"""95th percentile of (time offered - time due) over every packet the open
loop offered, in ms: how late the generator ran.  It tells a starved
generator apart from a fast server."""
import numpy as np

LAYER = "traffic generator"
UNIT = "ms"
MOVES = "rank_k_lag_p95_ms"


def read(run):
    lag = run.obs.get("gen_lag_s")
    if lag is None or lag.size == 0:
        return None
    return 1e3 * float(np.quantile(lag, 0.95, method="higher"))
