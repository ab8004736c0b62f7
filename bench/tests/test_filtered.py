"""The predicate plane: the rows' raw attributes, the filtered mix, the
reference with filters against a plain brute force over the raw
attributes, and the attribute words the program is given."""
import collections

import numpy as np
import pytest

from bench.harness import control, corpus, reference, system

from .helpers import BIG_SEED, tiny_cell

K = 10


def cell_data(seed=BIG_SEED):
    cell = tiny_cell("closed128-filtered")
    return cell, corpus.draw_cell(cell.config, cell.traffic, seed)


def raw_ok(data, where):
    """The clause on the raw attributes, written out for the test."""
    tenant, ts = data.attrs.tags["tenant"], data.attrs.ranges["ts"]
    ok = np.ones(len(ts), bool)
    for op, field, value in where or ():
        col = tenant if field == "tenant" else ts
        ok &= {"has": col == value, "lacks": col != value,
               "ge": col >= value, "lt": col < value}[op]
    return ok


def brute(data, q):
    ok = raw_ok(data, q.where) & data.policy.allowed(q.roles)
    ids = np.flatnonzero(ok)
    d = ((data.vectors[ids].astype(np.float64) - q.vector) ** 2).sum(1)
    top = np.lexsort((ids, d))[:q.k]
    return ids[top], d[top]


def test_attributes_follow_the_declared_schema():
    cell, data = cell_data()
    spec = cell.config["predicates"]
    tenant, ts = data.attrs.tags["tenant"], data.attrs.ranges["ts"]
    assert len(tenant) == len(ts) == cell.config["n_vectors"]
    assert tenant.min() >= 0 and tenant.max() < 40
    # Zipf(1.0): value 0 is the most frequent, about 1 / H_40 of the rows
    counts = np.bincount(tenant, minlength=40)
    assert counts.argmax() == 0
    assert counts[0] / len(tenant) == pytest.approx(
        corpus.tag_weights(spec["tags"]["tenant"])[0], abs=0.02)
    assert ts.min() >= 0.0 and ts.max() < 80.0
    again = corpus.draw_attributes(BIG_SEED, len(ts), spec)
    np.testing.assert_array_equal(again.ranges["ts"], ts)


def test_filtered_mix_draws_every_template_by_a_coin_per_query():
    cell, data = cell_data()
    edges = [float(e) for e in
             cell.config["predicates"]["ranges"]["ts"]["edges"]]
    shapes = collections.Counter()
    for q in data.pool:
        if q.where is None:
            continue
        shapes[tuple((op, f) for op, f, _ in q.where)] += 1
        for op, f, v in q.where:
            if f == "ts":
                assert v in edges and v > 0.0
        if [op for op, _, _ in q.where] == ["ge", "lt"]:
            assert q.where[0][2] < q.where[1][2]
    # a coin of 0.5 per query: binomial, sd 8 over a pool of 256
    n = len(data.pool)
    assert abs(sum(shapes.values()) - n / 2) < 4 * (n / 4) ** 0.5
    assert len(shapes) == 4 and min(shapes.values()) > 10
    # the coin, not a fixed pattern: another seed files other positions
    _, other = cell_data(7)
    assert [q.where is None for q in data.pool] != \
        [q.where is None for q in other.pool]


def test_reference_with_filters_is_the_brute_force():
    _, data = cell_data()
    filtered = [q for q in data.pool if q.where][:48]
    # a clause that leaves a role fewer than k rows, and one that leaves none
    for role, tenant in np.ndindex(8, 40):
        rare = corpus.QuerySpec(
            vector=data.pool[0].vector, roles=(role,), k=K,
            where=(("has", "tenant", tenant), ("lt", "ts", 40.0)))
        few = int((raw_ok(data, rare.where)
                   & data.policy.allowed(rare.roles)).sum())
        if 0 < few < K:
            break
    assert 0 < few < K
    none = corpus.QuerySpec(vector=data.pool[0].vector, roles=(0,), k=K,
                            where=(("ge", "ts", 30.0), ("lt", "ts", 10.0)))
    qs = filtered + [rare, none]
    ref = reference.Reference(data.vectors, data.policy.allowed,
                              data.attrs.eligible)
    got = ref.topk(qs, [q.k for q in qs])
    for q, (ids, d) in zip(qs, got):
        want_ids, want_d = brute(data, q)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_allclose(d, want_d, rtol=0, atol=1e-9)
    assert len(got[-2][0]) == few and len(got[-1][0]) == 0
    answers = [reference.Answer(ids=i, dists=d) for i, d in got]
    limits = {"dist_err": 0.02, "rank_gap": 0.02}
    assert reference.passed(reference.compare(ref, qs, answers, limits))
    # an answer padded to k past the eligible rows is not correct
    pad = [i for i in np.flatnonzero(data.policy.allowed(rare.roles))
           if i not in set(got[-2][0])][:K - few]
    ids = np.concatenate([got[-2][0], pad])
    padded = reference.Answer(ids=ids, dists=np.sort(ref.exact(
        rare.vector, ids)))
    checks = reference.compare(ref, [rare], [padded], limits)
    assert checks["unauthorized"]["value"] == 1


def test_attribute_words_hold_the_raw_attributes():
    from repro.core.predicate import predicate_pass
    cell, data = cell_data()
    schema = system.to_schema(cell.config["predicates"])
    assert schema.n_bits == 48 and schema.n_words == 2
    words = system.attr_words(schema, data.attrs)
    for q in [q for q in data.pool if q.where][:40]:
        prog = system.to_query(q)
        req, forb = schema.compile_where(prog.where)
        np.testing.assert_array_equal(predicate_pass(words, req, forb),
                                      raw_ok(data, q.where))


@pytest.mark.parametrize("seed", [11, 12])
def test_bf16_control_fails_the_limits_on_filtered_queries(seed):
    cell, data = cell_data(seed)
    qs = [q for q in data.pool if q.where][:64]
    ref = reference.Reference(data.vectors, data.policy.allowed,
                              data.attrs.eligible)
    checks = reference.compare(ref, qs, control.bf16_answers(ref, qs),
                               cell.config["limits"])
    assert not reference.passed(checks)
