"""Share of the ingest blocks' capacity that carried a packet, in %:
100 x the valid packets over slots x g_tick, summed over the window's
ticks, from the program's per-call records (``repro.obs``) of
``DecodeServer.tick``.  The scan runs over the whole block whatever its
fill.  None where the program keeps no such records."""
LAYER = "serve.FifoScheduler"
UNIT = "%"
MOVES = "rank_k_lag_p95_ms"


def read(run):
    from repro import obs
    last = getattr(obs, "last_records", None)
    recs = last("serve.tick", run.obs.get("ticks", 0)) if last else None
    if not recs:
        return None
    valid = sum(r.counts.get("serve.packets_ingested", 0) for r in recs)
    capacity = sum(r.counts.get("serve.block_capacity", 0) for r in recs)
    return 100.0 * valid / capacity if capacity else None
