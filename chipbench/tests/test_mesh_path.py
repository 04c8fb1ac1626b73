"""CPU rehearsal of the lane-sharded round cell on four virtual devices:
a sound run is correct; the control and each fault the cell can have are
not.  JAX has to be told of the four devices before it starts, so the
runs go in one child process: this file, run as a script."""
import json
import os
import subprocess
import sys

import pytest

CELL = "round.xsilo-xlstm125m.k16-4chip"
# 262,528 bytes: more than one 2^18-symbol chunk, so each device cuts
# two chunks from its own lanes, the last one padded
TINY = {"s": 8, "update": {"params": 65664, "bytes": 262528, "scale": 0.02,
                           "leaves": [["a", [64], "bfloat16"],
                                      ["b", [65600], "float32"]]}}
SOUND = ("sound", "sound_traced")
NOT_CORRECT = ("control", "byte_flipped", "half_batch", "decode_skipped",
               "one_chip_undecoded")


# -- faults planted under the timed path ------------------------------------

def _byte_flipped(orig):
    def fault(state, key):
        out = orig(state, key)
        return out.at[0, 0].set(out[0, 0] ^ 1)
    return fault


def _half_batch(orig):
    def fault(state, key):
        half = type(state)(**{**vars(state),
                              "P": state.P[: state.K // 2]})
        return orig(half, key)
    return fault


def _decode_skipped(orig):
    def fault(state, key):
        eng = state.engine
        return eng.matmul(eng.coding_matrix(key, state.K, state.K), state.P)
    return fault


def _one_chip_undecoded(orig):
    def fault(state, key):
        out = orig(state, key)
        share = state.L // state.P.sharding.num_devices
        return out.at[:, :share].set(0)
    return fault


FAULTS = {"byte_flipped": _byte_flipped, "half_batch": _half_batch,
          "decode_skipped": _decode_skipped,
          "one_chip_undecoded": _one_chip_undecoded}


def _runs() -> dict:
    """Every run of this file's tests, in this process."""
    import jax

    from chipbench import harness

    layout = harness.Layout()
    mod = layout.path("engine_round_mesh")
    entry = harness.cell_entry(layout.benchmark(), CELL)
    traffic = dict(layout.traffic(entry["traffic"]), clients=4)
    devices = jax.devices()[:4]
    out = {}
    for name in SOUND + NOT_CORRECT:
        orig = mod._program_round
        if name in FAULTS:
            mod._program_round = FAULTS[name](orig)
        try:
            r = harness.run_cell(CELL, 2**31 + 9, 0.3, name == "sound_traced",
                                 devices=devices, config=TINY,
                                 traffic=traffic, control=name == "control",
                                 log=lambda rec: None)
        finally:
            mod._program_round = orig
        out[name] = {"correct": r["correct"], "metrics": sorted(r["metrics"]),
                     "count": r["device"]["count"],
                     "checks": {c["name"]: c["value"] for c in r["checks"]}}
    return out


@pytest.fixture(scope="module")
def runs():
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(here))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src"), env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env,
                       cwd=root, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", SOUND)
def test_sound_run_is_correct(runs, name):
    from chipbench import harness
    r = runs[name]
    assert r["correct"] and r["count"] == 4
    assert r["checks"]["rounds_wrong"] == 0
    assert r["checks"]["rounds_checked"] >= 1
    bench = harness.Layout().benchmark()
    want = {m["name"] for m in harness.cell_metrics(
        bench, CELL, name == "sound_traced")}
    # the CPU runs the jnp kernels, whose names hold no "gf_matmul"
    assert set(r["metrics"]) == want - {
        "gf_kernel_ms.round", "gf_matmul_roofline", "peak_hbm_gb"}


@pytest.mark.parametrize("name", NOT_CORRECT)
def test_control_and_faults_are_caught(runs, name):
    r = runs[name]
    assert not r["correct"]
    assert r["checks"]["rounds_wrong"] >= 1 or name == "control"


if __name__ == "__main__":
    print(json.dumps(_runs()))
