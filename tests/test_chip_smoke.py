"""Rehearse ``chip_smoke.py`` on CPU: its phases at a tiny size (Pallas in
interpret mode), its label comparison, and its refusal to run without a
TPU."""
from __future__ import annotations

import dataclasses
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = types.SimpleNamespace(vocab=1024, nt_mean=20.0)


@pytest.fixture(scope="module")
def tiny_fits():
    docs = chip_smoke.make_docs(TINY, 420, seed=3, pad_to=32)
    fast = chip_smoke.fit_model(docs, k=8, backend="pallas", max_iter=3,
                                seed=3)
    ref = chip_smoke.fit_model(docs, k=8, backend="reference", max_iter=3,
                               seed=3)
    return docs, fast, ref


def test_phases_run_and_agree_on_cpu(tiny_fits):
    docs, fast, ref = tiny_fits
    assert fast.backend == "pallas" and ref.backend == "reference"
    out = chip_smoke.compare_fits(docs, fast, ref)
    assert out["n_diff"] == 0 and out["j_rel_diff"] <= 1e-5


def test_serve_phase_matches_predict(tiny_fits):
    docs, fast, _ = tiny_fits
    out = chip_smoke.serve_check(fast, docs, seed=1)
    assert out["rows"] == sum(chip_smoke.REQUEST_SIZES)
    assert out["mismatches"] == 0


def test_compare_rejects_a_difference_that_is_not_a_near_tie(tiny_fits):
    docs, fast, ref = tiny_fits
    gaps = chip_smoke.top2_gap(ref.index, docs, np.arange(docs.n_docs))
    row = int(np.argmax(gaps))                    # the clearest winner
    assert gaps[row] >= chip_smoke.TIE_GAP
    labels = np.array(fast.labels)
    labels[row] = (labels[row] + 1) % fast.k
    moved = dataclasses.replace(fast, labels=labels)
    with pytest.raises(AssertionError, match="not only at near-ties"):
        chip_smoke.compare_fits(docs, moved, ref)


def test_compare_rejects_an_objective_gap(tiny_fits):
    docs, fast, ref = tiny_fits
    shifted = dataclasses.replace(
        fast, rho_self=np.asarray(fast.rho_self) * (1 + 1e-3))
    with pytest.raises(AssertionError, match="objective differs"):
        chip_smoke.compare_fits(docs, shifted, ref)


def test_device_check_names_the_platform_it_found():
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        chip_smoke.device_check()


def _run_script(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_script_exits_nonzero_without_a_tpu(tmp_path):
    proc = _run_script(ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "platform 'cpu'" in proc.stderr


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_script(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
