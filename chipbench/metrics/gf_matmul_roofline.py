"""The GF matmul kernels' share of their memory roofline, in percent.

A round stands for two GF products over the (K, L) payload, the clients'
encode and the server's decode, each reading and writing K·L one-byte
symbols: 4·K·L bytes, counted from the cell's K and L alone, whatever
implements them.  The coefficient operands are left out.  No published
peak exists for GF(2^8) arithmetic, so the bound is HBM bandwidth.  On
several chips each chip moves its own lanes, 4·K·L / chips bytes, and
the kernel time is the mean over the chips (``profile.summarize``):
100 × (4·K·L / chips / peak bytes per second) / kernel seconds per round.
"""
LAYER = "kernels.gf_matmul"
UNIT = "%"
MOVES = "round_s"
MATCH = "gf_matmul"


def round_bytes(K: int, L: int) -> int:
    return 4 * K * L


def read(run):
    if run.trace is None or not run.obs.get("rounds"):
        return None
    t = run.trace.matched_s(MATCH)
    bw = run.peaks.get("hbm_bytes_per_s")
    if not t or not bw:
        return None
    chips = run.obs.get("chips", 1)
    least = round_bytes(run.obs["K"], run.obs["L"]) / chips / bw
    return 100.0 * least / (t / run.obs["rounds"])
