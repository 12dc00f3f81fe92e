"""Least time of a Lloyd iteration against hand counts, and the peak table."""
import ast
import json
from pathlib import Path

import pytest

from chipbench_testroot import REPO
from chipbench import peaks, roofline


def _config(name):
    return json.loads((REPO / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name, corpus_bytes, index_bytes", [
    # N x P x (4 + 4) + N x 4, and D x K x 4.
    ("pubmed8m", 131_072 * 128 * 8 + 131_072 * 4, 141_043 * 4096 * 4),
    ("nyt1m", 65_536 * 432 * 8 + 65_536 * 4, 495_126 * 1250 * 4),
])
def test_least_time_matches_hand_counts(name, corpus_bytes, index_bytes):
    c = _config(name)
    nnz = int(c["n_docs"] * c["nt_mean"])
    work = roofline.lloyd_iteration(n_docs=c["n_docs"],
                                    pad_width=c["pad_width"], nnz_total=nnz,
                                    dim=c["vocab"], k=c["k"])
    # Read the index, write and re-read the sums, write the new means.
    assert work["bytes"] == corpus_bytes + 4 * index_bytes
    assert work["flops"] == 2 * nnz * c["k"] + 3 * nnz
    least = roofline.least_time(work, peaks.peak("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(work["bytes"] / 819e9)


def test_hand_counts_of_pubmed8m_and_nyt1m():
    assert roofline.lloyd_iteration(
        n_docs=131_072, pad_width=128, nnz_total=0, dim=141_043,
        k=4096)["bytes"] == 9_378_136_064
    assert roofline.lloyd_iteration(
        n_docs=65_536, pad_width=432, nnz_total=0, dim=495_126,
        k=1250)["bytes"] == 10_129_274_560


def test_peak_table_has_the_v5e_row_and_refuses_unknown_kinds():
    p = peaks.peak("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s, p.hbm_bytes) == (197e12, 819e9,
                                                               16e9)
    assert "Google Cloud" in p.source
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def _imports(path: Path) -> set:
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
    return names


def test_the_yardstick_uses_no_program_code():
    """Only the drivers (the system under test) and the entry points reach
    the program; the generator, reference, comparison, peaks, least-time
    functions, trace reduction and readers import nothing of it."""
    reaches_program = {"drivers/fit.py", "run.py"}
    base = REPO / "chipbench"
    files = list(base.rglob("*.py"))
    assert len(files) > 10
    for f in files:
        used = {n for n in _imports(f) if n.split(".")[0] == "repro"}
        if f.relative_to(base).as_posix() in reaches_program:
            assert not (used - {"repro.cluster", "repro.sparse"}), (f, used)
        else:
            assert not used, (f, used)
