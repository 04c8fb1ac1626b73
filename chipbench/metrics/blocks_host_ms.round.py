"""Host milliseconds per decoded round in ``fednc.blocks``: cutting the
silos' expert slices into their generations' rows (one compiled program
per layout), from the program's per-call records (``repro.obs``) of the
window's ``fednc_round`` calls.  None where the program keeps no such
records or opens no such span."""
LAYER = "core.fednc and core.packets"
UNIT = "ms"
MOVES = "round_s"
SPANS = ("fednc.blocks",)


def read(run):
    from repro import obs
    last = getattr(obs, "last_records", None)
    rounds = run.obs.get("rounds")
    recs = last("fednc.round", run.obs["attempts"]) if last else None
    if not recs or not rounds or not any(s in r.spans for r in recs
                                         for s in SPANS):
        return None
    return sum(r.span_ms(*SPANS) for r in recs) / rounds
