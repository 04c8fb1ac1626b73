"""Host milliseconds per decoded round in ``fednc.aggregate``
(unpacketize and the weighted FedAvg sum), from the program's per-call
records (``repro.obs``) of the window's ``fednc_round`` calls.  None
where the program keeps no such records."""
LAYER = "core.fednc and core.packets"
UNIT = "ms"
MOVES = "round_s"
SPANS = ("fednc.aggregate",)


def read(run):
    from repro import obs
    last = getattr(obs, "last_records", None)
    rounds = run.obs.get("rounds")
    recs = last("fednc.round", run.obs["attempts"]) if last else None
    if not recs or not rounds:
        return None
    return sum(r.span_ms(*SPANS) for r in recs) / rounds
