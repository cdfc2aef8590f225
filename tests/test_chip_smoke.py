"""chip_smoke.py's phases on the CPU at ~20k fact rows.

The script itself refuses to run without a TPU; its phase functions
run here with the Pallas kernels in interpret mode, and must pass the
same checks: every handle resolves, MQO equals ``mqo=False`` equals the
NumPy reference, and nothing degraded.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

SCALE_ROWS = 20_000
BUDGET = 1 << 28


@pytest.fixture(scope="module")
def phases():
    return chip_smoke.run_single_chip(SCALE_ROWS, 0, BUDGET,
                                      jax.devices()[0])


@pytest.fixture(scope="module")
def small_catalog():
    from repro.relational.tpcds import generate_tpcds_catalog

    return generate_tpcds_catalog(SCALE_ROWS, 0)


def test_every_handle_resolves_in_both_passes(phases):
    for name in ("serve_cold", "serve_warm"):
        assert phases[name]["queries"] == 50
        assert phases[name]["windows"] == 7        # ceil(50 / 8)


def test_fact_columns_resident_after_first_window(phases):
    # F1 reads 3 fact columns, padded to the 2^15 capacity of 20k rows
    assert phases["first_window"]["fact_resident_bytes"] == 3 * 4 << 15


def test_warm_pass_reads_residents_and_batches(phases):
    warm = phases["serve_warm"]
    assert warm["resident_ce_reads"] > 0
    assert warm["batched_dispatches"] > 0
    assert warm["compiles"] == 0


def test_mqo_equals_mqo_off(phases):
    assert phases["check_mqo_off"]["equal"] is True


def test_reference_checks_every_family(phases):
    checked = phases["check_reference"]["per_family"]
    assert checked == {"F1": 10, "F2": 10, "F3": 8, "F4": 8, "F5": 6,
                       "F6": 8}
    assert phases["check_reference"]["worst_f32_error_over_bound"] <= 1.0


def test_pallas_route_ran_without_degradation(phases):
    assert phases["no_degradation"]["pallas_dispatches"] > 0


@pytest.mark.parametrize("q", [0, 10, 18, 20, 28, 36, 42])
def test_reference_rejects_a_perturbed_result(small_catalog, q):
    want, tol = chip_smoke.reference(small_catalog, q)
    got = {n: v.copy() for n, v in want.items()}
    last = list(got)[-1]
    got[last] = got[last].astype(np.float64)
    got[last][0] += max(1.0, abs(got[last][0]))
    with pytest.raises(AssertionError):
        chip_smoke.check_reference(got, want, tol)
    assert chip_smoke.check_reference(want, want, tol) == 0.0


def test_same_multiset_ignores_row_order():
    a = {"k": np.array([1, 2, 2]), "v": np.array([0.5, 1.5, 2.5])}
    b = {"k": np.array([2, 1, 2]), "v": np.array([2.5, 0.5, 1.5])}
    assert chip_smoke.same_multiset(a, b)
    b["v"][0] = 3.5
    assert not chip_smoke.same_multiset(a, b)


def test_main_exits_nonzero_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    assert capsys.readouterr().out == ""


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_smoke_imports_leave_xla_flags_alone():
    # launch/dryrun.py rewrites XLA_FLAGS on import; nothing the smoke
    # imports may pull it in
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = "--xla_dump_to=/nonexistent-marker"
    code = textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {os.path.abspath(ROOT)!r})
        import chip_smoke
        import repro.launch.compile_cache, repro.launch.mesh
        import repro.relational.tpcds, repro.relational.datagen
        assert "repro.launch.dryrun" not in sys.modules
        assert os.environ["XLA_FLAGS"] == "--xla_dump_to=/nonexistent-marker"
        print("IMPORTS_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "IMPORTS_OK" in out.stdout


def test_sharded_phase_on_four_host_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.abspath(ROOT)!r})
        import chip_smoke
        out = chip_smoke.run_sharded({SCALE_ROWS}, 0, {BUDGET}, 4)
        assert out["check_sharded"]["equal"] is True
        print("SHARDED_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
