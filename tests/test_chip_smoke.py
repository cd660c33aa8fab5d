"""chip_smoke.py's phases at tiny sizes on the CPU.

The smoke script only runs for real on a TPU; running each phase here keeps
its entry points, checks and reporting from rotting between chip runs. The
four-chip phase runs in a child process on four forced host devices.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _last_report(out: str, phase: str) -> dict:
    lines = [ln for ln in out.splitlines() if ln.startswith(f"[{phase}] ")]
    assert lines, out
    return json.loads(lines[-1][len(phase) + 3:])


def test_main_refuses_a_backend_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    cap = capsys.readouterr()
    assert "JAX found no TPU" in cap.err
    assert '"ok"' not in cap.out


@pytest.mark.parametrize("phase,kwargs", [
    ("assignment", {"n": 200}),
    ("ot", {"side": 10}),
    ("reference", {"n": 64}),
    ("serving", {"requests": 6, "lo": 8, "hi": 40}),
    ("kernels", {"n": 144, "batch": 2}),
])
def test_phase_runs_and_certifies(smoke, capsys, phase, kwargs):
    result = getattr(smoke, f"phase_{phase}")(0, **kwargs)
    rep = _last_report(capsys.readouterr().out, phase)
    assert rep == json.loads(json.dumps(result, default=smoke._plain))
    if phase in ("assignment", "ot"):
        assert rep["dual_feasible"]
        assert rep["additive_gap"] <= rep["additive_gap_bound"]
        assert rep["wall_s"] > 0
    if phase == "serving":
        assert rep["max_additive_gap_ratio"] <= 1.0
    if phase == "kernels":
        assert {k for k, v in rep.items() if isinstance(v, dict)} == {
            "cost_matrix", "cost_matrix_batched", "slack_propose",
            "slack_propose_batched", "sinkhorn_row_update",
            "fused_assignment_phases", "fused_ot_phases"}
        assert rep["fused_assignment_phases"]["phases"] > 8
        assert rep["fused_ot_phases"]["phases"] > 1


def test_certify_rejects_a_gap_beyond_the_bound(smoke):
    class Fake:
        cost = 1.0

        def additive_gap(self):
            return 2.0

        def additive_gap_bound(self):
            return 1.0

        def dual_feasible(self):
            return True

    with pytest.raises(smoke.SmokeFailure, match="exceeds the bound"):
        smoke.certify(Fake(), "fake")


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes to
    the fixed directory inside the checkout."""
    import jax
    from repro.launch.platform import DEFAULT_CACHE_DIR, use_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-outside")
    assert use_compile_cache() == "cache-from-outside"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert use_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert updates == [("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))]
    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


def test_four_chip_phase_on_forced_host_devices():
    code = ("import chip_smoke as cs; "
            "cs.phase_four_chips(0, side=8, requests=8, lo=8, hi=24, "
            "spread_n=16)")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=ROOT,
        env={"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    rep = _last_report(proc.stdout, "four_chips")
    assert rep["batch_ragged"]["devices"] == 4
    assert len(rep["batch_uniform"]["bytes_per_device"]) == 4
    assert len(rep["matrix"]["bytes_per_device"]) == 4
    assert rep["matrix"]["dual_feasible"]


def test_dev0_excess(smoke):
    def mem(*peaks):
        return [{"device": i, "peak_bytes_in_use": p}
                for i, p in enumerate(peaks)]

    assert smoke.dev0_excess(mem(10, 4, 7, 3)) == 3
    assert smoke.dev0_excess(mem(5, 4, 7, 3)) == -2
    assert smoke.dev0_excess(mem(None, None, None, None)) is None
