"""The K-blocked reference against the whole reference at CPU size, with the
means split over four of the test process's CPU devices, and the bfloat16
control of the mesh cell's check."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testroot import FIXTURES, REPO
from chipbench import check, reference, reference_kblocks
from chipbench.corpus import make_corpus, spec_of
from chipbench.drivers import mesh_fit as mesh_driver
from chipbench.drivers import sub_seeds

TINY = json.loads((FIXTURES / "tiny.json").read_text())
MESH = json.loads((REPO / "chipbench" / "traffic" / "mesh_fit.json")
                  .read_text())
DEVICES = jax.devices()[:4]


@pytest.fixture(scope="module", params=[5, 2**31 + 3])
def fits(request):
    seeds = sub_seeds(request.param)
    c = make_corpus(spec_of(TINY), seeds["corpus"])
    kw = dict(k=TINY["k"], dim=c.dim, seed=seeds["fit"],
              max_iter=MESH["max_iter"])
    return (reference.lloyd(c.ids, c.vals, c.nnz, **kw),
            reference_kblocks.lloyd(c.ids, c.vals, c.nnz, devices=DEVICES,
                                    **kw))


def test_blocks_give_the_whole_references_labels(fits):
    whole, blocked = fits
    assert np.array_equal(blocked.labels, whole.labels)


def test_blocks_give_the_whole_references_means_and_objectives(fits):
    whole, blocked = fits
    assert [b.devices() for b in blocked.blocks] == [{d} for d in DEVICES]
    means = np.concatenate([np.asarray(b) for b in blocked.blocks], axis=1)
    np.testing.assert_allclose(means, np.asarray(whole.means_t), rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(blocked.objectives, whole.objectives,
                               rtol=1e-6)


def test_a_tie_across_blocks_goes_to_the_lowest_id_and_empty_means_stay():
    """Two kinds of document, each repeated: the seed means of one kind are
    equal, so every document ties between means of several blocks.  The
    lowest id among them wins, and the other means, left empty, keep their
    seed."""
    n, k, dim, p = 32, 8, 16, 4
    ids = np.zeros((n, p), np.int32)
    ids[0::2] = [0, 1, 2, 3]
    ids[1::2] = [4, 5, 6, 7]
    vals = np.full((n, p), 0.5, np.float32)
    nnz = np.full((n,), p, np.int32)
    kind = np.arange(n) % 2
    seed = next(s for s in range(100) if all(
        len({j // 2 for j in np.nonzero(
            kind[np.asarray(reference.seed_rows(n, k, s))] == t)[0]}) > 1
        for t in (0, 1)))
    seeded = kind[np.asarray(reference.seed_rows(n, k, seed))]
    first = [int(np.argmax(seeded == t)) for t in (0, 1)]
    kw = dict(k=k, dim=dim, seed=seed, max_iter=4)
    blocked = reference_kblocks.lloyd(ids, vals, nnz, devices=DEVICES, **kw)
    whole = reference.lloyd(ids, vals, nnz, **kw)
    assert np.array_equal(blocked.labels, np.asarray(first)[kind])
    assert np.array_equal(blocked.labels, whole.labels)
    means = np.concatenate([np.asarray(b) for b in blocked.blocks], axis=1)
    seeds = np.asarray(reference._seed_means(
        jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(nnz),
        reference.seed_rows(n, k, seed), k=k, dim=dim, store=jnp.float32))
    empty = np.setdiff1d(np.arange(k), first)
    assert np.array_equal(means[:, empty], seeds[:, empty])
    np.testing.assert_allclose(means, np.asarray(whole.means_t), atol=1e-6)


@pytest.mark.parametrize("seed", [5, 2**31 + 3])
def test_bfloat16_control_fails_the_mesh_cells_limits(seed):
    numbers = mesh_driver.control(dict(TINY), MESH, seed)
    ok, _ = check.judge(numbers,
                        check.load_limits(REPO, "nyt1m.mesh_fit"))
    assert not ok, numbers
