"""The benchmark's copies of the generators, the reference, and the
control that the limits must separate from the program."""
import numpy as np
import pytest

from bench.harness import control, corpus, reference
from repro.core import generate_policy

from .helpers import BIG_SEED


@pytest.mark.parametrize("seed", [1, 9])
def test_policy_copy_draws_what_the_repository_draws(seed):
    ours = corpus.draw_policy(20_000, 40, 60, (1.0, 2.0), (2.0, 1.5), 5, seed)
    theirs = generate_policy(20_000, n_roles=40, n_permissions=60,
                             block_zipf=(1.0, 2.0), perm_zipf=(2.0, 1.5),
                             seed=seed)
    assert ours.block_roles == theirs.block_roles
    for b, members in enumerate(theirs.block_members):
        np.testing.assert_array_equal(ours.members(b), members)


def test_same_seed_same_inputs_and_large_seeds():
    a = corpus.draw_vectors(BIG_SEED, 5000, 16, 8, 4.0)
    b = corpus.draw_vectors(BIG_SEED, 5000, 16, 8, 4.0)
    c = corpus.draw_vectors(BIG_SEED + 1, 5000, 16, 8, 4.0)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.dtype == np.float32 and a.shape == (5000, 16)


def _setup(seed, n=5000, dim=16):
    draw = corpus.draw_policy(n, 40, 60, (1.0, 2.0), (2.0, 1.5), 5, 1)
    vecs = corpus.draw_vectors(seed, n, dim, 8, 4.0)
    qs = corpus.draw_queries(seed, 64, vecs, draw, 10, 0.2, 0.1)
    ref = reference.Reference(vecs, draw.allowed)
    return vecs, qs, ref


def brute(vecs, q, mask):
    ids = np.flatnonzero(mask)
    d = ((vecs[ids].astype(np.float64) - q.vector) ** 2).sum(1)
    top = np.lexsort((ids, d))[:q.k]
    return ids[top], d[top]


def test_reference_is_the_exact_brute_force():
    vecs, qs, ref = _setup(3)
    got = ref.topk(qs, [q.k for q in qs])
    for q, (ids, d) in zip(qs, got):
        want_ids, want_d = brute(vecs, q, ref.mask(q.roles))
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_allclose(d, want_d, rtol=0, atol=1e-9)
    answers = [reference.Answer(ids=i, dists=d) for i, d in got]
    checks = reference.compare(ref, qs, answers, {"dist_err": 0.02,
                                                  "rank_gap": 0.02})
    assert reference.passed(checks)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_bf16_control_fails_the_limits(seed):
    _, qs, ref = _setup(seed)
    checks = reference.compare(ref, qs, control.bf16_answers(ref, qs),
                               {"dist_err": 0.02, "rank_gap": 0.02})
    assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]
    assert not reference.passed(checks)
