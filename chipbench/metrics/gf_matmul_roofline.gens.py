"""The GF matmul kernels' share of their memory roofline, in percent, in
a round over several generations of unequal K.

Generation g's encode and decode each read and write K_g·L_g one-byte
symbols, so a round moves 4·Σ_g K_g·L_g bytes (``obs["coded_symbols"]``
is Σ_g K_g·L_g, counted from the cell's layout alone); the coefficient
operands are left out.  The bound is HBM bandwidth (no published peak
exists for GF(2^8) arithmetic), and the kernel time is
``gf_matmul_roofline``'s: ops whose op or module name holds
``gf_matmul``, per decoded round.  None where the run has no such count
or no such kernel time."""
LAYER = "kernels.gf_matmul"
UNIT = "%"
MOVES = "round_s"
MATCH = "gf_matmul"


def read(run):
    if run.trace is None or not run.obs.get("rounds") \
            or not run.obs.get("coded_symbols"):
        return None
    t = run.trace.matched_s(MATCH)
    bw = run.peaks.get("hbm_bytes_per_s")
    if not t or not bw:
        return None
    least = 4 * run.obs["coded_symbols"] / bw
    return 100.0 * least / (t / run.obs["rounds"])
