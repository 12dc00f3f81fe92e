"""The benchmark's copy of the generator and its plain reference against
the program, at CPU size: the copy makes the program's corpus, and the
reference fit gives the program's labels."""
import json

import numpy as np
import pytest

from chipbench_testroot import FIXTURES, REPO
from chipbench import reference
from chipbench.corpus import make_corpus, spec_of

TINY = json.loads((FIXTURES / "tiny.json").read_text())


def test_copy_makes_the_programs_corpus():
    from repro.data import CorpusSpec as ProgramSpec, make_corpus as program

    spec = spec_of(TINY)
    ours = make_corpus(spec, 9)
    docs, df, _, _ = program(ProgramSpec(
        n_docs=spec.n_docs, vocab=spec.vocab, nt_mean=spec.nt_mean,
        pad_to=spec.pad_to, n_topics=spec.n_topics, seed=9))
    assert np.array_equal(np.asarray(ours.ids), np.asarray(docs.ids))
    assert np.array_equal(np.asarray(ours.nnz), np.asarray(docs.nnz))
    assert np.allclose(np.asarray(ours.vals), np.asarray(docs.vals),
                       rtol=1e-6, atol=1e-7)
    assert np.array_equal(np.asarray(ours.df_sorted), np.asarray(df))


@pytest.mark.parametrize("name", ["pubmed8m", "nyt1m"])
def test_documents_hold_the_published_distinct_terms(name):
    """Mean distinct terms per document within 5% of the paper's nt, at the
    configuration's own vocabulary and pad width."""
    cfg = json.loads((REPO / "chipbench" / "configs" / f"{name}.json")
                     .read_text())
    spec = spec_of(dict(cfg, n_docs=2048))
    nnz = np.asarray(make_corpus(spec, 2**31 + 17).nnz)
    assert abs(nnz.mean() / cfg["nt_mean"] - 1) < 0.05
    assert nnz.max() < cfg["pad_width"]


def test_reference_fit_gives_the_programs_labels():
    from repro.cluster import ClusterConfig, fit
    from repro.sparse import SparseDocs

    corpus = make_corpus(spec_of(TINY), 4)
    model = fit(SparseDocs(ids=corpus.ids, vals=corpus.vals, nnz=corpus.nnz,
                           dim=corpus.dim, _df=corpus.df_sorted),
                ClusterConfig(k=TINY["k"], max_iter=5, seed=17))
    ref = reference.lloyd(corpus.ids, corpus.vals, corpus.nnz, k=TINY["k"],
                          dim=corpus.dim, seed=17, max_iter=5)
    assert np.array_equal(np.asarray(model.labels), ref.labels)
    assert np.allclose([h["objective"] for h in model.history],
                       ref.objectives, rtol=1e-6)
