"""Synthetic corpora with the paper's universal characteristics.

The benchmark's own copy of the program's generator, so that a change to the
program cannot move the inputs.  Term draws follow a Zipf law over the
vocabulary, boosted per latent topic; each document's distinct terms and
their counts come from the host (numpy, from the seed), and the document
frequencies, tf-idf weighting, L2 normalisation and df-rank remap of term
ids run on the device.

For the same seed the corpus is the one the program's generator makes: the
host draws consume the random stream in the same order.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    n_docs: int
    vocab: int
    nt_mean: float
    pad_to: int | None = None
    zipf_alpha: float = 1.05
    n_topics: int = 64
    topic_sharpness: float = 200.0
    draw_factor: float = 1.6     # term draws per distinct term wanted


@dataclasses.dataclass
class HostDocs:
    """Raw documents in original term ids: (N, P) ids and counts, (N,) nnz."""
    ids: np.ndarray
    counts: np.ndarray
    nnz: np.ndarray


@dataclasses.dataclass
class Corpus:
    """A weighted corpus on the device."""
    ids: object          # (N, P) int32, df-rank ids, ascending within a row
    vals: object         # (N, P) float32, unit rows, 0 on padding
    nnz: object          # (N,) int32
    df_sorted: object    # (D,) int32, df of each df-rank id
    dim: int

    @property
    def n_docs(self) -> int:
        return int(self.ids.shape[0])

    @property
    def pad_width(self) -> int:
        return int(self.ids.shape[1])


def _zipf_probs(vocab: int, alpha: float) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    return p / p.sum()


def _topic_probs(spec: CorpusSpec, rng) -> np.ndarray:
    """(T, D): the Zipf base with a random head set boosted per topic."""
    base = _zipf_probs(spec.vocab, spec.zipf_alpha)
    n_head = max(4, spec.vocab // 256)
    boost = np.ones((spec.n_topics, spec.vocab))
    for t in range(spec.n_topics):
        head = rng.choice(spec.vocab, size=n_head, replace=False)
        boost[t, head] *= spec.topic_sharpness
    topic_p = base[None, :] * boost
    topic_p /= topic_p.sum(axis=1, keepdims=True)
    return topic_p


def _draw(spec: CorpusSpec, topic_p: np.ndarray, rng, n_docs: int,
          pad: int | None) -> HostDocs:
    """Topic, length and term draws of ``n_docs`` documents."""
    vocab = spec.vocab
    topics = rng.integers(0, spec.n_topics, size=n_docs)
    lengths = np.clip(rng.poisson(spec.nt_mean * spec.draw_factor,
                                  size=n_docs), 8, None)
    if pad is None:
        pad = int(np.quantile(lengths, 0.999) + 8)
    ids = np.zeros((n_docs, pad), np.int32)
    counts = np.zeros((n_docs, pad), np.float32)
    nnz = np.zeros((n_docs,), np.int32)
    # One draw per topic for all of its documents, in topic order; each
    # document's distinct terms ascend by term id.
    for t in range(spec.n_topics):
        (docs_t,) = np.nonzero(topics == t)
        if docs_t.size == 0:
            continue
        lens = lengths[docs_t]
        draws = rng.choice(vocab, size=int(lens.sum()), replace=True,
                           p=topic_p[t])
        owner = np.repeat(np.arange(docs_t.size, dtype=np.int64), lens)
        keys, cnt = np.unique(owner * vocab + draws, return_counts=True)
        row, term = np.divmod(keys, vocab)
        first = np.searchsorted(row, np.arange(docs_t.size))
        slot = np.arange(keys.size) - first[row]
        keep = slot < pad
        ids[docs_t[row[keep]], slot[keep]] = term[keep]
        counts[docs_t[row[keep]], slot[keep]] = cnt[keep]
        nnz[docs_t] = np.minimum(np.bincount(row, minlength=docs_t.size),
                                 pad)
    return HostDocs(ids=ids, counts=counts, nnz=nnz)


@partial(jax.jit, static_argnames=("dim",))
def _df_of(ids, nnz, *, dim):
    live = jnp.arange(ids.shape[1])[None, :] < nnz[:, None]
    parked = jnp.where(live, ids, dim)
    return jnp.zeros((dim + 1,), jnp.int32).at[parked.reshape(-1)].add(1)[:dim]


@jax.jit
def _order(df):
    perm = jnp.argsort(df, stable=True)          # perm[new] = old
    inv = jnp.argsort(perm, stable=True)         # inv[old] = new
    return df[perm], inv


@jax.jit
def _weigh(ids, counts, nnz, df, n_total, inv):
    """tf-idf (tf * log(N / df)), unit rows, df-rank ids re-sorted ascending
    within each row with padding last."""
    p = ids.shape[1]
    dim = df.shape[0]
    live = jnp.arange(p)[None, :] < nnz[:, None]
    idf = jnp.log(n_total / jnp.maximum(df.astype(jnp.float32), 1.0))
    vals = jnp.where(live, counts * idf[ids], 0.0)
    norm = jnp.sqrt(jnp.sum(vals * vals, axis=1) + 1e-12)
    vals = vals / norm[:, None]
    new_ids = inv[ids]
    key = jnp.where(live, new_ids, dim)
    idx = jnp.argsort(key, axis=1, stable=True)
    new_ids = jnp.take_along_axis(jnp.where(live, new_ids, 0), idx, axis=1)
    new_vals = jnp.take_along_axis(jnp.where(live, vals, 0.0), idx, axis=1)
    return new_ids.astype(jnp.int32), new_vals


def make_corpus(spec: CorpusSpec, seed: int) -> Corpus:
    """The corpus of ``seed``: host draws, then weighting on the device."""
    rng = np.random.default_rng(seed)
    topic_p = _topic_probs(spec, rng)
    raw = _draw(spec, topic_p, rng, spec.n_docs, spec.pad_to)
    ids = jnp.asarray(raw.ids)
    nnz = jnp.asarray(raw.nnz)
    df = _df_of(ids, nnz, dim=spec.vocab)
    df_sorted, inv = _order(df)
    new_ids, vals = _weigh(ids, jnp.asarray(raw.counts), nnz, df,
                          jnp.float32(spec.n_docs), inv)
    return Corpus(ids=new_ids, vals=vals, nnz=nnz, df_sorted=df_sorted,
                  dim=spec.vocab)


def spec_of(config: dict) -> CorpusSpec:
    """The corpus a configuration file states."""
    return CorpusSpec(n_docs=int(config["n_docs"]), vocab=int(config["vocab"]),
                      nt_mean=float(config["nt_mean"]),
                      pad_to=config.get("pad_width"),
                      zipf_alpha=float(config.get("zipf_alpha", 1.05)),
                      n_topics=int(config.get("n_topics", 64)),
                      topic_sharpness=float(config.get("topic_sharpness",
                                                       200.0)),
                      draw_factor=float(config.get("draw_factor", 1.6)))
