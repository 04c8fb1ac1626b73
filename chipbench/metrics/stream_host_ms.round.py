"""Host milliseconds per decoded round in ``engine.encode`` and
``engine.decode``: slicing, dispatching and joining the chunks of the
round's streamed kernel products.  From the program's per-call records
(``repro.obs``) of the window's ``fednc_round`` calls; None where the
program keeps no such records."""
LAYER = "engine.CodingEngine"
UNIT = "ms"
MOVES = "round_s"
SPANS = ("engine.encode", "engine.decode")


def read(run):
    from repro import obs
    last = getattr(obs, "last_records", None)
    rounds = run.obs.get("rounds")
    recs = last("fednc.round", run.obs["attempts"]) if last else None
    if not recs or not rounds:
        return None
    return sum(r.span_ms(*SPANS) for r in recs) / rounds
