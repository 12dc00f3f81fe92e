"""The program's own tracing (``repro.obs``): spans in the profiler's trace,
per-fit counters in the history, and phase names on the fused fit's ops."""
import glob
import re

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from repro import obs
from repro.cluster import ClusterConfig, fit
from repro.core import lloyd

CFG = ClusterConfig(k=8, max_iter=6, seed=0, batch_size=256)
SPANS = {"repro.fit", "repro.lloyd.prepare", "repro.lloyd.iteration",
         "repro.estparams", "repro.lloyd.pull", "repro.lloyd.fused",
         "repro.lloyd.finish"}
COUNTERS = ("compiles", "compile_s", "cache_hits", "estparams_s")


@pytest.fixture(scope="module")
def docs():
    from repro.data import CorpusSpec, make_corpus
    return make_corpus(CorpusSpec(n_docs=600, vocab=512, nt_mean=20,
                                  n_topics=8, seed=1))[0]


def _repro_events(path: str) -> list:
    """[(name, start_ns, end_ns, args)] of the host events named repro.*"""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_a_fit_puts_nested_spans_with_their_args_into_the_profile(
        docs, tmp_path):
    fit(docs, CFG)                       # warm, so the traced fit is short
    with jax.profiler.trace(str(tmp_path)):
        model = fit(docs, CFG)
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = _repro_events(path)
    assert {e[0] for e in events} == SPANS
    assert model.n_iter > 2              # the fused remainder ran

    by = {name: [e for e in events if e[0] == name] for name in SPANS}
    [top] = by["repro.fit"]
    assert all(e[3]["fit"] == top[3]["fit"] for e in events)
    assert all(_inside(e, top) for e in events)
    iters = sorted(by["repro.lloyd.iteration"], key=lambda e: e[1])
    assert [e[3]["iteration"] for e in iters] == [1, 2]
    assert len(by["repro.estparams"]) == 2
    for est, it in zip(sorted(by["repro.estparams"], key=lambda e: e[1]),
                       iters):
        assert _inside(est, it)
    [fused] = by["repro.lloyd.fused"]
    pulls = by["repro.lloyd.pull"]
    assert len(pulls) == 3               # one per prologue iteration + fused
    assert sum(_inside(p, fused) for p in pulls) == 1
    assert sum(_inside(p, it) for p in pulls for it in iters) == 2


def test_history_compiles_sum_to_what_a_listener_counted(docs):
    seen = {"compiles": 0, "compile_s": 0.0, "hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1
            seen["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        # k=7 has shapes no other fit here used: the fit compiles.
        res = lloyd.lloyd_fit(docs, k=7, max_iter=5, batch_size=256, seed=3)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    rows = res.history
    assert sum(r["compiles"] for r in rows) == seen["compiles"] > 0
    assert sum(r["compile_s"] for r in rows) == pytest.approx(
        seen["compile_s"])
    assert sum(r["cache_hits"] for r in rows) == seen["hits"]


def test_each_row_holds_its_share_of_the_fit(docs):
    fit(docs, CFG)
    rows = fit(docs, CFG).history        # warm: no compile outside EstParams
    assert len(rows) > 2
    assert all(set(COUNTERS) <= set(r) for r in rows)
    # EstParams runs in iterations 1 and 2 only, and its span is timed.
    assert all(r["estparams_s"] > 0 for r in rows[:2])
    assert all(r["estparams_s"] == 0 for r in rows[2:])
    assert all(r["compiles"] == 0 and r["compile_s"] == 0 for r in rows[2:])


def test_counters_count_only_while_a_fit_is_open():
    assert obs.open_fit() is None
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    with obs.fit() as rec:
        with obs.fit() as inner:
            assert inner is rec          # a fit inside a fit joins it
        jax.jit(lambda x: x * 5 - 2)(jnp.arange(7.0)).block_until_ready()
        with obs.span("probe", step=1):
            pass
    assert obs.open_fit() is None
    assert rec.compiles + rec.cache_hits >= 1
    assert rec.span_s["probe"] >= 0 and set(rec.span_s) == {"probe"}
    with obs.fit() as other:
        assert other.id != rec.id


def test_the_fused_fit_compiles_under_its_name_with_phase_scopes(docs):
    from repro.core.meanindex import StructuralParams
    from repro.core.update import init_state

    k, bs = 8, 200                       # 600 rows: no padding needed
    state = init_state(docs, k, StructuralParams.trivial(docs.dim), seed=0)
    valid = jnp.arange(docs.n_docs) < docs.n_docs
    hlo = lloyd._fused_fit_fn("esicp", "reference", bs, k, 3).lower(
        state, docs, valid, jnp.asarray(1, jnp.int32), None
    ).compile().as_text()
    assert hlo.startswith("HloModule jit_lloyd_fused_fit,")
    op_names = set(re.findall(r'op_name="([^"]+)"', hlo))
    for scope in ("assign", "update.sums", "update.normalize", "update.index",
                  "update.rho", "update.bounds", "lloyd.diag"):
        assert any(f"/{scope}/" in n or n.endswith(f"/{scope}")
                   for n in op_names), scope
