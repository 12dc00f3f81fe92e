"""The control of the fit check: the plain reference put in the program's
place in bfloat16 must fail the cells' limits, and in float32 it must pass
them."""
import json

import jax.numpy as jnp
import pytest

from chipbench_testroot import FIXTURES, REPO
from chipbench import check, reference
from chipbench.corpus import make_corpus, spec_of
from chipbench.drivers import fit as fit_driver
from chipbench.drivers import sub_seeds

TINY = json.loads((FIXTURES / "tiny.json").read_text())
FIT = json.loads((FIXTURES / "tiny_fit.json").read_text())


@pytest.mark.parametrize("seed", [5, 2**31 + 3])
def test_bfloat16_fit_fails_the_fit_limits(seed):
    numbers = fit_driver.control(TINY, FIT, seed)
    for cell in ("pubmed8m.fit", "nyt1m.fit"):
        ok, _ = check.judge(numbers, check.load_limits(REPO, cell))
        assert not ok, (cell, numbers)


def test_sub_seeds_are_fixed_by_the_seed_and_fit_32_bits():
    s = sub_seeds(2**33 + 5)
    assert s == sub_seeds(2**33 + 5) and s != sub_seeds(2**33 + 6)
    assert 0 <= s["fit"] < 2**31


def test_float32_reference_in_the_programs_place_passes():
    seeds = sub_seeds(5)
    corpus = make_corpus(spec_of(TINY), seeds["corpus"])
    fit = reference.lloyd(corpus.ids, corpus.vals, corpus.nnz, k=TINY["k"],
                          dim=corpus.dim, seed=seeds["fit"],
                          max_iter=FIT["max_iter"], store=jnp.float32)
    numbers = check.fit_numbers(corpus, k=TINY["k"], seed=seeds["fit"],
                                max_iter=FIT["max_iter"],
                                fits=[(fit.labels, fit.objectives)],
                                means_t=fit.means_t, ref=fit)
    ok, _ = check.judge(numbers, check.load_limits(REPO, "pubmed8m.fit"))
    assert ok and numbers["update_gap"] == 0.0
