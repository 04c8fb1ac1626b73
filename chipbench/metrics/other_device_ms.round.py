"""Device-busy milliseconds per decoded round outside the GF matmul
kernels: packetize, pads, concatenations, unpacketize and aggregate."""
LAYER = "core.fednc and core.packets"
UNIT = "ms"
MOVES = "round_s"
MATCH = "gf_matmul"


def read(run):
    if run.trace is None or not run.obs.get("rounds"):
        return None
    other = run.trace.busy_s - (run.trace.matched_s(MATCH) or 0.0)
    return 1e3 * other / run.obs["rounds"] if other > 0 else None
