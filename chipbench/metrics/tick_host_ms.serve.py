"""Host milliseconds per tick in ``serve.schedule`` (assembling the
padded block from the queues) and ``serve.complete`` (payload readback,
digest, slot close and admission), from the program's per-call records
(``repro.obs``) of the window's ``DecodeServer.tick`` calls.  None where
the program keeps no such records."""
LAYER = "serve.FifoScheduler"
UNIT = "ms"
MOVES = "rank_k_lag_p95_ms"
SPANS = ("serve.schedule", "serve.complete")


def read(run):
    from repro import obs
    last = getattr(obs, "last_records", None)
    ticks = run.obs.get("ticks", 0)
    recs = last("serve.tick", ticks) if last else None
    if not recs:
        return None
    return sum(r.span_ms(*SPANS) for r in recs) / ticks
