"""repro.analysis: rule fixtures, suppressions, contract failures.

Each FNC rule gets doctored source that must fire at the expected
line (and a near-miss that must stay clean), the suppression marker
is exercised both honored and ignored, the contract checker is fed
deliberately broken registry entries (wrong dtype, shape drift,
orphaned seeded kernel), and the whole repo is asserted to lint
clean — the same gate ``python -m repro.analysis`` enforces in CI.
"""
import json
import pathlib
import textwrap

import pytest

from repro.analysis import (ANALYSIS_SCHEMA, analyze_source,
                            check_kernel_contracts,
                            check_registry_docstring, run_analysis)
from repro.analysis.__main__ import main as analysis_main
from repro.engine import registry

ROOT = pathlib.Path(__file__).resolve().parent.parent


def rules_at(rel, source):
    """[(rule, line)] of kept findings for one fixture module."""
    findings, _ = analyze_source(rel, textwrap.dedent(source))
    return [(f.rule, f.line) for f in findings]


# ---------------------------------------------------------------------------
# FNC001 raw-clock
# ---------------------------------------------------------------------------

def test_fnc001_fires_on_raw_clock():
    src = """\
    import time
    t0 = time.perf_counter()
    """
    assert rules_at("src/repro/engine/x.py", src) == [("FNC001", 2)]


def test_fnc001_sees_through_import_aliases():
    src = """\
    from time import perf_counter as pc
    t0 = pc()
    """
    assert rules_at("benchmarks/bench_x.py", src) == [("FNC001", 2)]


def test_fnc001_exempts_obs():
    src = """\
    import time
    t0 = time.perf_counter()
    """
    assert rules_at("src/repro/obs/trace.py", src) == []


# ---------------------------------------------------------------------------
# FNC002 unfenced-timing
# ---------------------------------------------------------------------------

_TIMED = """\
import jax.numpy as jnp
from repro import obs

def bench(A, B):
    with obs.timed("matmul") as sw:
        C = jnp.dot(A, B)
    {tail}
    return C
"""


def test_fnc002_fires_on_unfenced_region():
    src = _TIMED.format(tail="")
    assert rules_at("benchmarks/bench_x.py", src) == [("FNC002", 5)]


def test_fnc002_clean_when_fenced():
    src = _TIMED.replace("C = jnp.dot(A, B)",
                         "C = sw.fence(jnp.dot(A, B))").format(tail="")
    assert rules_at("benchmarks/bench_x.py", src) == []


def test_fnc002_clean_on_spans():
    """Spans are host phases by design: they never fence, and the rule
    looks only at ``obs.timed`` walls."""
    src = _TIMED.replace('obs.timed("matmul") as sw',
                         'obs.span("matmul")').format(tail="")
    assert rules_at("src/repro/engine/x.py", src) == []


def test_fnc002_clean_when_region_does_no_jax_work():
    src = """\
    from repro import obs

    def bench(xs):
        with obs.timed("sort") as sw:
            out = sorted(xs)
        return out
    """
    assert rules_at("benchmarks/bench_x.py", src) == []


# ---------------------------------------------------------------------------
# FNC003 tracer-leak
# ---------------------------------------------------------------------------

def test_fnc003_fires_on_host_cast_in_jit():
    src = """\
    import jax

    @jax.jit
    def f(x):
        return float(x) + 1.0
    """
    assert rules_at("src/repro/core/x.py", src) == [("FNC003", 5)]


def test_fnc003_fires_on_python_branch_and_item():
    src = """\
    import jax

    @jax.jit
    def f(x):
        if x > 0:
            return x.item()
        return x
    """
    assert rules_at("src/repro/core/x.py", src) == [
        ("FNC003", 5), ("FNC003", 6)]


def test_fnc003_fires_in_helper_reachable_from_jit():
    src = """\
    import jax
    import numpy as np

    def helper(x):
        return np.asarray(x)

    @jax.jit
    def f(x):
        return helper(x)
    """
    assert rules_at("src/repro/core/x.py", src) == [("FNC003", 5)]


def test_fnc003_static_argnames_exempt():
    src = """\
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("s",))
    def f(x, *, s):
        if s == 1:
            return x
        return x + s
    """
    assert rules_at("src/repro/core/x.py", src) == []


def test_fnc003_shape_control_flow_is_static():
    src = """\
    import jax

    @jax.jit
    def f(x):
        n, k = x.shape
        if k > 4:
            return x[:, :4]
        return x
    """
    assert rules_at("src/repro/core/x.py", src) == []


def test_fnc003_plain_functions_not_flagged():
    src = """\
    def f(x):
        return float(x)
    """
    assert rules_at("src/repro/core/x.py", src) == []


# ---------------------------------------------------------------------------
# FNC004 unseeded-rng
# ---------------------------------------------------------------------------

def test_fnc004_fires_in_scoped_paths():
    src = """\
    import random
    import numpy as np
    a = np.random.rand(3)
    b = random.random()
    """
    assert rules_at("src/repro/sim/x.py", src) == [
        ("FNC004", 3), ("FNC004", 4)]


def test_fnc004_seeded_generator_is_sanctioned():
    src = """\
    import numpy as np
    rng = np.random.default_rng(0)
    a = rng.random(3)
    b = np.random.Generator(np.random.PCG64(1))
    """
    assert rules_at("src/repro/serve/x.py", src) == []


def test_fnc004_out_of_scope_paths_ignored():
    src = """\
    import numpy as np
    a = np.random.rand(3)
    """
    assert rules_at("src/repro/data/x.py", src) == []


# ---------------------------------------------------------------------------
# FNC005 dtype-discipline
# ---------------------------------------------------------------------------

def test_fnc005_fires_on_promoted_dtypes():
    src = """\
    import jax.numpy as jnp

    def k(A):
        f = A.astype(jnp.float32)
        z = jnp.zeros((2, 2), jnp.float16)
        return f, z
    """
    assert rules_at("src/repro/kernels/gf_custom.py", src) == [
        ("FNC005", 4), ("FNC005", 5)]


def test_fnc005_resolves_module_dtype_constants():
    src = """\
    import jax.numpy as jnp
    _ACC_DTYPE = jnp.float32

    def k(A):
        return A.astype(_ACC_DTYPE)
    """
    assert rules_at("src/repro/kernels/gf_custom.py", src) == [
        ("FNC005", 5)]


def test_fnc005_field_dtypes_clean_and_scope_limited():
    src = """\
    import jax.numpy as jnp

    def k(A):
        packed = A.astype(jnp.int32)
        return jnp.zeros((2, 2), dtype=jnp.uint8), packed
    """
    assert rules_at("src/repro/kernels/gf_custom.py", src) == []
    # float math is the whole point outside the GF modules
    bad = "import jax.numpy as jnp\nx = jnp.zeros((2,), jnp.float32)\n"
    assert rules_at("src/repro/kernels/flash_attention.py", bad) == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_honored_and_audited():
    src = ("import time\n"
           "t0 = time.time()  # fednc: ignore[FNC001] epoch anchor\n")
    findings, suppressed = analyze_source("src/repro/core/x.py", src)
    assert findings == []
    (s,) = suppressed
    assert (s.rule, s.line, s.justification) == (
        "FNC001", 2, "epoch anchor")


def test_suppression_must_name_the_rule():
    src = ("import time\n"
           "t0 = time.time()  # fednc: ignore[FNC002] wrong id\n")
    findings, suppressed = analyze_source("src/repro/core/x.py", src)
    assert [f.rule for f in findings] == ["FNC001"]
    assert suppressed == []


# ---------------------------------------------------------------------------
# contract checker: doctored registry entries
# ---------------------------------------------------------------------------

def _register_temp(name, fn, seeded=False):
    registry.register_kernel(name, fn, seeded=seeded)
    return name


def test_contract_wrong_dtype_detected():
    import jax.numpy as jnp

    name = _register_temp(
        "ctr_bad_dtype",
        lambda A, P, *, s: jnp.zeros(
            (A.shape[0], P.shape[1]), jnp.int32))
    try:
        violations, summary = check_kernel_contracts(kernels=[name])
        assert violations and all(v.rule == "CTR001" for v in violations)
        assert any("dtype" in v.message for v in violations)
        assert summary["violations"]
    finally:
        registry.unregister_kernel(name)


def test_contract_shape_drift_detected():
    import jax.numpy as jnp

    name = _register_temp(
        "ctr_bad_shape",
        lambda A, P, *, s: jnp.zeros(
            (A.shape[0], P.shape[1] + 1), jnp.uint8))
    try:
        violations, _ = check_kernel_contracts(kernels=[name])
        assert violations and all(v.rule == "CTR001" for v in violations)
        assert any("shape" in v.message for v in violations)
    finally:
        registry.unregister_kernel(name)


def test_contract_orphan_seeded_kernel_detected():
    import jax.numpy as jnp

    name = _register_temp(
        "ctr_orphan_seeded",
        lambda seeds, P, *, s: jnp.zeros(
            (seeds.shape[0], P.shape[1]), jnp.uint8),
        seeded=True)
    try:
        violations, _ = check_kernel_contracts(kernels=[name])
        assert any(v.rule == "CTR002"
                   and "sibling" in v.message for v in violations)
    finally:
        registry.unregister_kernel(name)


def test_contract_seeded_suffix_required():
    import jax.numpy as jnp

    name = _register_temp(
        "ctr_sneaky",
        lambda seeds, P, *, s: jnp.zeros(
            (seeds.shape[0], P.shape[1]), jnp.uint8),
        seeded=True)
    try:
        violations, _ = check_kernel_contracts(kernels=[name])
        assert any(v.rule == "CTR002" and "suffix" in v.message
                   for v in violations)
    finally:
        registry.unregister_kernel(name)


def test_contract_pass_leaves_no_tracer_residue():
    """eval_shape-ing the registry must not poison process caches.

    get_field's lru_cache fills on first use; if that first use is
    the contract checker's abstract trace, the cached tables must
    still be concrete arrays — a leaked tracer here breaks every
    later real decode in the process."""
    import jax.numpy as jnp

    from repro.core.gf import get_field

    get_field.cache_clear()
    violations, _ = check_kernel_contracts()
    assert violations == []
    A = jnp.array([[2]], dtype=jnp.uint8)
    P = jnp.array([[7]], dtype=jnp.uint8)
    assert int(registry.gf_matmul(A, P, s=8, kernel="jnp")[0, 0]) == 14


def test_registry_docstring_drift_detected(monkeypatch):
    doc = registry.__doc__
    assert check_registry_docstring() == []      # in sync today
    monkeypatch.setattr(
        registry, "__doc__",
        doc.replace("``jnp_packed``", "``jnp_unpacked``"))
    findings = check_registry_docstring()
    assert {f.rule for f in findings} == {"CTR003"}
    assert any("jnp_packed" in f.message for f in findings)
    assert any("jnp_unpacked" in f.message for f in findings)


def test_unregister_kernel_guards():
    with pytest.raises(ValueError, match="reserved alias"):
        registry.unregister_kernel("auto")
    with pytest.raises(ValueError, match="not registered"):
        registry.unregister_kernel("never_was")


# ---------------------------------------------------------------------------
# whole-repo gate + CLI
# ---------------------------------------------------------------------------

def test_repo_lints_clean_with_empty_baseline():
    report = run_analysis(ROOT)
    assert report["schema"] == ANALYSIS_SCHEMA
    assert report["findings"] == []
    assert report["ok"] is True
    assert report["files_scanned"] > 50
    # every honored suppression must carry a justification (auditable
    # empty baseline: zero findings, zero unexplained ignores)
    assert all(s["justification"] for s in report["suppressed"])
    assert report["contracts"]["points_checked"] > 0
    assert "jnp_packed_seeded" in report["contracts"]["kernels"]


def test_cli_reports_failure_and_writes_json(tmp_path, capsys):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "x.py").write_text("import time\nt = time.time()\n")
    out = tmp_path / "r.json"
    rc = analysis_main(["--root", str(tmp_path), "--json", str(out),
                        "--no-contracts"])
    assert rc == 1
    report = json.loads(out.read_text())
    assert report["ok"] is False
    assert report["counts_by_rule"] == {"FNC001": 1}
    assert "FNC001" in capsys.readouterr().err


def test_cli_ok_on_clean_tree(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "x.py").write_text("x = 1\n")
    rc = analysis_main(["--root", str(tmp_path), "--no-contracts"])
    assert rc == 0
