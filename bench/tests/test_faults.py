"""The timed path broken underneath a whole run: ``correct`` comes out
false.  The faults a retrieval cell can have: an answer altered where it
is produced (a launch returns another row's id), half of a batch left
out (a launch answers only the first half of its query rows), and, in a
filtered cell, the ``where`` clause dropped (a launch scans without its
require/forbid rows)."""
import numpy as np
import pytest

from bench.harness import runner
from repro.ann.scorescan import ScoreScanIndex

from .helpers import BIG_SEED, tiny_cell


def altered(inner):
    def search_masked_batch(self, qs, k, role_masks, bounds=None, **kw):
        d, i = inner(self, qs, k, role_masks, bounds=bounds, **kw)
        first = i[:, 0]
        other = np.where(first == self.ids[0], self.ids[-1], self.ids[0])
        i[:, 0] = np.where(first >= 0, other, first)
        return d, i
    return search_masked_batch


def half_left_out(inner):
    def search_masked_batch(self, qs, k, role_masks, bounds=None, **kw):
        d, i = inner(self, qs, k, role_masks, bounds=bounds, **kw)
        h = (len(qs) + 1) // 2
        d[h:], i[h:] = np.inf, -1
        return d, i
    return search_masked_batch


def where_dropped(inner):
    def search_masked_batch(self, qs, k, role_masks, bounds=None, **kw):
        kw.pop("require", None)
        kw.pop("forbid", None)
        return inner(self, qs, k, role_masks, bounds=bounds, **kw)
    return search_masked_batch


@pytest.mark.parametrize("fault,mix", [
    (altered, "closed128"), (half_left_out, "closed128"),
    (altered, "closed128-filtered"), (half_left_out, "closed128-filtered"),
    (where_dropped, "closed128-filtered")])
def test_broken_path_is_not_correct(monkeypatch, fault, mix):
    monkeypatch.setattr(ScoreScanIndex, "search_masked_batch",
                        fault(ScoreScanIndex.search_masked_batch))
    code, res = runner.run_cell(tiny_cell(mix), BIG_SEED,
                                1.0, False, platforms=("cpu",))
    assert code == 0
    assert res["correct"] is False
    bad = [n for n, c in res["checks"].items() if c["value"] > c["limit"]]
    assert bad, res["checks"]
