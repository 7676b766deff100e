"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases and checks pass at a tiny size (the one check that needs a TPU, the
Mosaic custom call in the fused step's HLO, is replaced here)."""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")

TINY = dict(n_samples=700, n_markers=1024, n_traits=300, batch_markers=512,
            n_causal=4, effect_size=0.5, kernel_markers=256,
            lmm_samples=300, lmm_traits=32, lmm_effect_size=0.6)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve through it
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "needs a TPU" in err
    assert '"ok"' not in out


def test_one_chip_phases_pass_at_tiny_size(smoke, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "check_fused_hlo", lambda sizes: None)
    sizes = dataclasses.replace(smoke.Sizes(), **TINY)
    smoke.run_one_chip(sizes, 0, str(tmp_path), smoke.Phases())
    out = capsys.readouterr().out
    for phase in ("[cohort]", "[fused scan]", "[dense scan]", "[check]", "[lmm scan]"):
        assert phase in out


def test_reference_check_catches_a_wrong_best_marker(smoke, tmp_path, monkeypatch):
    """The float64 comparison is not vacuous: a per-trait best moved to
    another marker fails it."""
    sizes = dataclasses.replace(smoke.Sizes(), **TINY)
    cohort, paths = smoke.write_cohort(
        sizes, 0, str(tmp_path / "c"), n_samples=sizes.n_samples,
        n_traits=sizes.n_traits, effect_size=sizes.effect_size)
    out = str(tmp_path / "scan")
    smoke.scan(paths, out, "--engine", "dense", "--batch-markers", "512")
    ref = smoke.OlsReference(cohort, 8)
    r_ref = ref.r()
    smoke.check_against_reference("dense", out, ref, r_ref)
    best = os.path.join(out, "per_trait_best.tsv")
    with open(best) as f:
        lines = f.readlines()
    trait, marker, nlp = lines[1].rstrip("\n").split("\t")
    other = int(marker[2:]) ^ 1
    lines[1] = f"{trait}\trs{other:08d}\t{nlp}\n"
    with open(best, "w") as f:
        f.writelines(lines)
    with pytest.raises(smoke.SmokeFailure, match="best marker differs"):
        smoke.check_against_reference("dense", out, ref, r_ref)


def test_four_chip_phase_on_fake_devices(tmp_path):
    child = textwrap.dedent(
        f"""
        import dataclasses, importlib.util, os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        spec = importlib.util.spec_from_file_location("chip_smoke", {_SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        import sys; sys.modules["chip_smoke"] = smoke
        spec.loader.exec_module(smoke)
        sizes = dataclasses.replace(smoke.Sizes(), **{TINY!r})
        smoke.run_four_chips(sizes, 0, {str(tmp_path)!r}, smoke.Phases())
        """
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", child], capture_output=True,
                          text=True, timeout=600, env=env, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "byte-identical across 4 and 1 devices" in proc.stdout
    assert proc.stdout.count("claimed") == 4
