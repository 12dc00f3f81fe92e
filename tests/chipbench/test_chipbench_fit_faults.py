"""A fit cell with its timed path broken underneath: the harness runs as on
the chip (only the chip check is replaced) and ``correct`` must come out
false for each fault a fit can have."""
import dataclasses

import jax.numpy as jnp
import pytest

from chipbench_testroot import TINY_FIT, make_root, run_cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("fit_faults"))


@pytest.fixture
def fresh_programs():
    """Broken code must be traced anew, and must not outlive the test: the
    prologue calls ``update_step`` from Python, the fused remainder is a
    cached jit that has to be built again."""
    from repro.core import lloyd

    lloyd._fused_fit_fn.cache_clear()
    yield
    lloyd._fused_fit_fn.cache_clear()


def _state_unchanged(real):
    def step(docs, assign, prev_assign, prev_state, params, **kw):
        return prev_state
    return step


def _half_batch(real):
    """The update sums only the first half of the documents: the means are
    taken over the rest of the batch left in."""
    def step(docs, assign, prev_assign, prev_state, params, **kw):
        keep = jnp.arange(docs.ids.shape[0]) < docs.ids.shape[0] // 2
        half = dataclasses.replace(
            docs, vals=jnp.where(keep[:, None], docs.vals, 0.0))
        return real(half, assign, prev_assign, prev_state, params, **kw)
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_broken_update_step_is_not_correct(root, monkeypatch, fresh_programs,
                                           fault):
    from repro.core import lloyd

    monkeypatch.setattr(lloyd, "update_step", fault(lloyd.update_step))
    out = run_cell(monkeypatch, root, TINY_FIT, seconds=0.2)
    assert out["correct"] is False


def test_answer_altered_where_it_is_produced(root, monkeypatch,
                                            fresh_programs):
    """One label moved to another cluster after the fit: a single document
    of thousands, caught because the means no longer match the labels."""
    from repro.cluster import strategies

    real = strategies.lloyd_fit

    def altered(*args, **kw):
        res = real(*args, **kw)
        labels = res.assign.copy()
        labels[0] = (labels[0] + 1) % kw["k"]
        res.assign = labels
        return res

    monkeypatch.setattr(strategies, "lloyd_fit", altered)
    out = run_cell(monkeypatch, root, TINY_FIT, seconds=0.2)
    assert out["correct"] is False
    assert out["checks"]["update_gap"]["value"] > \
        out["checks"]["update_gap"]["limit"]
