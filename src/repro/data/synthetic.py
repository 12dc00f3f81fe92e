"""UC-faithful synthetic corpus generator.

PubMed / NYT are not shipped offline, so benchmarks and tests run on synthetic
corpora engineered to reproduce the paper's universal characteristics (§III):

* Zipf's law on term frequency *and* document frequency (Fig. 2a) — term draws
  follow ``p(s) ∝ rank^-alpha``;
* high dimensionality with (nt̂/D) << 1 sparsity;
* tf-idf weighting + L2 normalisation (Eq. 15) which, combined with the Zipf
  draw, yields the feature-value concentration phenomenon in cluster means
  (Fig. 4/9) — verified by ``benchmarks/fig2_ucs.py``;
* a latent topic mixture so that K-means finds real structure (clusters are
  annotated by a few dominant terms, exactly the paper's observation).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp

from repro.sparse import SparseDocs, tf_idf, l2_normalize_rows, remap_terms_by_df, df_counts, with_df


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    n_docs: int = 20_000
    vocab: int = 8_192
    nt_mean: float = 60.0        # paper PubMed: 58.96 distinct terms / doc
    zipf_alpha: float = 1.05     # exponent of the rank-frequency law
    n_topics: int = 64           # latent clusters (drives mean concentration)
    # Calibrated so clustering means reproduce the paper's feature-value
    # concentration + Pareto CPS (benchmarks/fig4_cps.py: CPS(0.1) ≈ 0.91
    # vs paper 0.92 on PubMed).
    topic_sharpness: float = 200.0
    pad_to: int | None = None
    seed: int = 0


def _zipf_probs(vocab: int, alpha: float) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    return p / p.sum()


def make_corpus(spec: CorpusSpec):
    """Returns (docs: SparseDocs tf-idf L2-normalised df-rank-remapped,
    df: (D,) int32, perm: new->old term permutation, topics: (N,) labels)."""
    rng = np.random.default_rng(spec.seed)
    base = _zipf_probs(spec.vocab, spec.zipf_alpha)

    # Topic-specific distributions: boost a random "head set" per topic so each
    # cluster mean concentrates on a few dominant terms (paper Fig. 4a).
    n_head = max(4, spec.vocab // 256)
    topic_boost = np.ones((spec.n_topics, spec.vocab))
    for t in range(spec.n_topics):
        head = rng.choice(spec.vocab, size=n_head, replace=False)
        topic_boost[t, head] *= spec.topic_sharpness
    topic_p = base[None, :] * topic_boost
    topic_p /= topic_p.sum(axis=1, keepdims=True)

    topics = rng.integers(0, spec.n_topics, size=spec.n_docs)
    lengths = np.clip(rng.poisson(spec.nt_mean * 1.6, size=spec.n_docs), 8, None)

    pad = spec.pad_to or int(np.quantile(lengths, 0.999) + 8)
    ids = np.zeros((spec.n_docs, pad), np.int32)
    vals = np.zeros((spec.n_docs, pad), np.float32)
    nnz = np.zeros((spec.n_docs,), np.int32)

    # One draw per topic for all of its documents: Generator.choice with
    # ``p`` consumes one uniform per sample, in order, so this is exactly the
    # per-document draws concatenated (same seed -> same corpus).  Each
    # document's distinct terms and their counts come from one np.unique
    # over (document, term) keys, ascending by term within the document.
    for t in range(spec.n_topics):
        (docs_t,) = np.nonzero(topics == t)
        if docs_t.size == 0:
            continue
        lens = lengths[docs_t]
        draws = rng.choice(spec.vocab, size=int(lens.sum()), replace=True,
                           p=topic_p[t])
        owner = np.repeat(np.arange(docs_t.size, dtype=np.int64), lens)
        keys, counts = np.unique(owner * spec.vocab + draws,
                                 return_counts=True)
        row, term = np.divmod(keys, spec.vocab)
        first = np.searchsorted(row, np.arange(docs_t.size))
        slot = np.arange(keys.size) - first[row]
        keep = slot < pad
        ids[docs_t[row[keep]], slot[keep]] = term[keep]
        vals[docs_t[row[keep]], slot[keep]] = counts[keep]
        nnz[docs_t] = np.minimum(np.bincount(row, minlength=docs_t.size),
                                 pad)

    docs = SparseDocs(ids=jnp.asarray(ids), vals=jnp.asarray(vals), nnz=jnp.asarray(nnz), dim=spec.vocab)
    df = df_counts(docs)
    docs = tf_idf(docs, df=df)
    docs = l2_normalize_rows(docs)
    docs, perm = remap_terms_by_df(docs, df=df)
    df_sorted = df[perm]
    # The permuted counts ARE the remapped corpus's df: seed the .df cache
    # so the fit path (EstParams, tf-idf consumers) never recounts.
    docs = with_df(docs, df_sorted)
    return docs, df_sorted, perm, jnp.asarray(topics)
