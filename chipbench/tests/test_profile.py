"""The trace reduction: intervals, the gf_matmul name rule, idle share and
the roofline's byte count, on a recorded TPU trace and a CPU trace."""
import glob
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, profile

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tiny_round.xplane.pb")


def test_union_intersect_complement_by_hand():
    u = profile.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert profile.length(u) == 6
    assert profile.intersect(u, [(2, 6)]) == [(2, 3), (5, 6)]
    assert profile.complement([(0, 10)], u) == [(3, 5), (8, 10)]


def test_idle_share_is_one_minus_busy_union_over_window():
    ops = [profile.Op("d", "m", "a", 0, 4), profile.Op("d", "m", "b", 2, 6),
           profile.Op("d", "jit_gf_matmul_x", "k", 8, 9),
           profile.Op("d", "m", "c", 30, 40)]          # outside the window
    tr = profile.Trace(ops, [("bench.round", 0, 10), ("bench.round", 20, 25)],
                       ["d"])
    s = profile.summarize(tr, ["bench.round"], ["bench.round"], ["gf_matmul"])
    assert s.window_s == pytest.approx(15e-9)
    assert s.busy_s == pytest.approx(7e-9)              # [0,6] + [8,9]
    assert s.matched_s("gf_matmul") == pytest.approx(1e-9)
    assert [g[1] for g in s.idle_gaps] == pytest.approx([5e-9, 2e-9, 1e-9])


def test_op_and_module_names():
    assert profile.op_short_name(
        "%gf_matmul_packed.1 = s32[8,65536]{1,0} custom-call(s32[1,8,8] "
        "%convert_bitcast_fusion)") == "gf_matmul_packed.1"
    # an op that only *reads* the kernel's output is not the kernel
    name = profile.op_short_name(
        "%and_convert_fusion.3 = (u8[8,65536]) fusion(s32[8,65536] "
        "%gf_matmul_packed.1), kind=kLoop")
    assert "gf_matmul" not in name
    assert profile.module_short_name(
        "jit_gf_matmul_pallas_packed(12963660691787347379)") \
        == "jit_gf_matmul_pallas_packed"


def test_recorded_tpu_trace():
    tr = profile.read(FIXTURE)
    assert tr.devices == ["/device:TPU:0"]
    assert [n for n, _, _ in tr.spans] == ["bench.round", "bench.check"]
    kernel = [o for o in tr.ops if "gf_matmul" in o.name]
    assert {o.name for o in kernel} == {"gf_matmul_packed.1"}
    assert all(o.module == "jit_gf_matmul_pallas_packed" for o in kernel)
    s = profile.summarize(tr, ["bench.round"], ["bench.round"],
                          ["gf_matmul"])
    # the module rule also counts the kernel module's pack/unpack fusions
    k_op = sum(o.end - o.start for o in kernel) * 1e-9
    assert k_op < s.matched_s("gf_matmul") < s.busy_s < s.window_s
    assert 0 < s.busy_s / s.window_s < 1
    assert s.device_ops[0][1] >= s.device_ops[-1][1]


def test_cpu_trace_matches_gf_matmul_by_module_name(tmp_path):
    @jax.jit
    def gf_matmul_probe(x):
        return (x * 3 + 1).sum()

    @jax.jit
    def other(x):
        return (x - 1).max()

    x = jnp.ones((256, 256))
    gf_matmul_probe(x).block_until_ready()
    other(x).block_until_ready()
    opts = harness._profile_options()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.round"):
        gf_matmul_probe(x).block_until_ready()
        other(x).block_until_ready()
    jax.profiler.stop_trace()
    pb = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    tr = profile.read(pb)
    assert tr.devices == ["/host:CPU"]
    assert {o.module for o in tr.ops} >= {"jit_gf_matmul_probe", "jit_other"}
    s = profile.summarize(tr, ["bench.round"], [], ["gf_matmul"])
    assert 0 < s.matched_s("gf_matmul") < s.busy_s


def _reader(name):
    path = os.path.join(os.path.dirname(__file__), "..", "metrics",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_roofline_bytes_from_the_cells_shapes():
    roof = _reader("gf_matmul_roofline")
    # xsilo-xlstm125m: K=8, L = 392,749,056 bf16 bytes = symbols at s=8
    assert roof.round_bytes(8, 392_749_056) == 12_567_969_792
    # xdevice-cnn: K=128, L = 1,237,160 f32 bytes
    assert roof.round_bytes(128, 1_237_160) == 633_425_920


def test_roofline_share_from_kernel_time():
    roof = _reader("gf_matmul_roofline")
    summary = profile.Summary(10.0, 2.0, {"gf_matmul": 0.3}, [], [], 0)
    run = harness.Run("c", {}, {}, {"rounds": 2, "K": 8, "L": 1000},
                      1.0, None, {"hbm_bytes_per_s": 1e9}, summary)
    # least time 32,000 B / 1e9 B/s = 32 us against 150 ms per round
    assert roof.read(run) == pytest.approx(100 * 32e-6 / 0.15)
    run.trace = None
    assert roof.read(run) is None


def test_roofline_counts_each_chips_share_of_the_bytes():
    roof = _reader("gf_matmul_roofline")
    s = profile.summarize(profile.read(FIXTURE), ["bench.round"],
                          ["bench.round"], ["gf_matmul"])

    def read(**chips):
        return roof.read(harness.Run(
            "c", {}, {}, {"rounds": 1, "K": 8, "L": 1 << 20, **chips}, 1.0,
            None, {"hbm_bytes_per_s": 819e9}, s))

    one = read()
    # the one-chip reading as it was before chips were counted
    assert one == 100.0 * (roof.round_bytes(8, 1 << 20) / 819e9) \
        / (s.matched_s("gf_matmul") / 1)
    assert read(chips=1) == one
    assert read(chips=4) == pytest.approx(one / 4, rel=1e-12)
