"""Device milliseconds per decoded round in the GF matmul kernels: ops
whose op or HLO-module name contains ``gf_matmul``, inside the window."""
LAYER = "kernels.gf_matmul"
UNIT = "ms"
MOVES = "round_s"
MATCH = "gf_matmul"


def read(run):
    if run.trace is None or not run.obs.get("rounds"):
        return None
    t = run.trace.matched_s(MATCH)
    return 1e3 * t / run.obs["rounds"] if t else None
