"""95th percentile, over the packets the window's ticks ingested, of
(end of the ingesting tick - the packet's offer stamp), in ms: how long
a packet waits in the scheduler's queue and its tick.  From the
program's per-call records (``repro.obs``) of ``DecodeServer.tick``;
None where the program keeps no such records."""
import numpy as np

LAYER = "serve.FifoScheduler"
UNIT = "ms"
MOVES = "rank_k_lag_p95_ms"


def read(run):
    from repro import obs
    last = getattr(obs, "last_records", None)
    recs = last("serve.tick", run.obs.get("ticks", 0)) if last else None
    if not recs:
        return None
    waits = [w for r in recs for w in r.samples.get("serve.queue_wait_s", ())]
    if not waits:
        return None
    return 1e3 * float(np.quantile(waits, 0.95, method="higher"))
