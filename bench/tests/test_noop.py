"""A configuration without ``predicates`` and a mix without
``filtered_share`` draw exactly what the benchmark drew before the
predicate plane, and warm the same calls: the vectors, the query pool, the
sampled check indices, the reference answers and the warm-up calls of the
tiny configuration under ``closed128``, pinned by hash."""
import hashlib

import numpy as np
import pytest

from bench.harness import corpus, reference, system

from .helpers import BIG_SEED, tiny_cell

PINNED = {
    BIG_SEED: dict(
        vectors="d064dfda8f5b403775e4c38675b623e946f450ecdab3093152f7bb881cef7abf",
        queries="c62f4e5a48fe15f62bc13ab90f5750b4baa6f2c37dd023425167f79d02732ab0",
        pick="1d0538dec674c9f6b5d8e583e2ae269640f825bdafcc918191d71d5087ff6c64",
        reference="6dd4cd10b498bf73ea8be33eba69df976869c8592aff153fa20dfe17a0119dc1"),
    7: dict(
        vectors="cbbe2b25ce2b73e8d04aa2b8fd1ead17381fef219585142b5e7354d1645f1859",
        queries="f33f439ab04dbf07fd526ccd27c4f90945c0e429f5800d11d658c70bc9b9cd19",
        pick="e61c3624416e5b2ef4da23bfe647b26adf1c37ec14806f7cef9c17a0292d0aed",
        reference="caa7060a572b610a207d0a8ba66bc2156626afa607dad22276b5bdb471b17384"),
}
WARM_CALLS = (42, "7fce00e232726c8d346f87123f2dd5f30876121ba84a2e1677c589405a7199d3")


def digest(*arrays):
    return hashlib.sha256(b"".join(np.ascontiguousarray(a).tobytes()
                                   for a in arrays)).hexdigest()


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_unfiltered_cell_draws_the_same_data(seed):
    cell = tiny_cell("closed128")
    data = corpus.draw_cell(cell.config, cell.traffic, seed)
    assert data.attrs is None
    assert all(q.where is None for q in data.pool)
    pick = corpus.check_pick(seed, 1000, cell.traffic["check_sample"])
    ref = reference.Reference(data.vectors, data.policy.allowed)
    first = data.pool[:64]
    answers = ref.topk(first, [q.k for q in first])
    got = dict(
        vectors=digest(data.vectors),
        queries=digest(*[np.concatenate([
            q.vector.view(np.uint32).astype(np.int64),
            np.array(q.roles + (q.k,), np.int64)]) for q in data.pool]),
        pick=digest(pick.astype(np.int64)),
        reference=digest(*[np.concatenate([i.astype(np.int64),
                                           d.view(np.int64)])
                           for i, d in answers]))
    assert got == PINNED[seed]


def test_unfiltered_store_warms_the_same_calls():
    cell = tiny_cell("closed128")
    cfg, tr = cell.config, cell.traffic
    data = corpus.draw_cell(cfg, tr, BIG_SEED)
    built = system.build(cfg, data.vectors, data.policy, data.attrs)
    calls = []
    for e in built.engines:
        def record(qs, k, masks, bounds=None, _n=len(e), **kw):
            calls.append((_n, len(qs), k, bounds is None, masks.shape,
                          kw.get("require") is None))
        e.search_masked_batch = record
    n = system.warm(built, tr["k"], tr["max_batch"], cfg["dim"], BIG_SEED)
    assert (n, hashlib.sha256(repr(calls).encode()).hexdigest()) == \
        WARM_CALLS


@pytest.mark.parametrize("share,max_batch,variants", [
    (0.5, 16, {False, True}),     # a flush of 16 is unfiltered 1 in 65,536
    (0.5, 64, {True}),            # a flush of 64: 5e-20, never
    (0.0, 64, {False})])
def test_filtered_store_warms_the_variants_its_flushes_take(
        share, max_batch, variants):
    cell = tiny_cell("closed128-filtered")
    cfg, tr = cell.config, cell.traffic
    data = corpus.draw_cell(cfg, tr, BIG_SEED)
    built = system.build(cfg, data.vectors, data.policy, data.attrs)
    calls = []
    for e in built.engines:
        def record(qs, k, masks, bounds=None, **kw):
            calls.append((len(qs), kw.get("require")))
        e.search_masked_batch = record
    n = system.warm(built, tr["k"], max_batch, cfg["dim"], BIG_SEED,
                    corpus.flush_kinds(share, max_batch))
    assert n == len(calls)
    assert {r is not None for _, r in calls} == variants
    assert all(r.shape == (b, 2) for b, r in calls if r is not None)
    assert max(b for b, _ in calls) == max_batch
