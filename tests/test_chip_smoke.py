"""``chip_smoke.py`` rehearsed on CPU at a tiny size.

The script's phases run here with the kernel interpreted, so a change to
the store, the scheduler or the sharded path that would break the chip run
fails in the test suite first.  The script itself refuses to run off a TPU;
that refusal is checked too.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(n_vectors=2500, n_roles=6, n_permissions=12,
                        wide_vectors=1500, wide_roles=40,
                        wide_permissions=48, n_requests=32, max_batch=16,
                        lam_threshold=250)


def test_refuses_without_a_tpu():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not a TPU" in r.stderr


def test_one_chip_phases_pass_on_cpu(capsys):
    assert chip_smoke.run_single(TINY, seed=0)
    out = capsys.readouterr().out
    for phase in ("[kernel] ", "[warmup] ", "[search] ", "[serve] ",
                  "[wide] "):
        assert phase in out
    assert "mismatches=0" in out


def test_sharded_phase_passes_on_forced_host_devices():
    """Two forced CPU devices: shards and launch outputs on distinct
    devices, answers identical to the one-device store."""
    code = ("import sys, chip_smoke\n"
            f"sizes = chip_smoke.Sizes(**{json.dumps(TINY.__dict__)})\n"
            "sys.exit(0 if chip_smoke.run_sharded(sizes, 0, 2) else 1)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert "2 devices, imbalance" in r.stdout
    assert "0 off-device outputs" in r.stdout


@pytest.mark.parametrize("tie", [False, True], ids=["far", "tie"])
def test_checker_allows_only_stated_ties(tie):
    """The oracle check accepts an id swap only between distances that tie
    within the stated tolerance, and rejects an unauthorized hit."""
    import numpy as np
    from repro.core import Query, SearchResult

    class Policy:
        def authorized_mask(self, r):
            return np.arange(4) != 3             # row 3 is not authorized

    gap = chip_smoke.TIE_ATOL / 4 if tie else 1.0
    vecs = np.array([[0.0], [1.0], [np.sqrt(1.0 + gap)], [0.5]])
    check = chip_smoke.Checker("t", chip_smoke.Oracle(vecs, Policy()))
    q = Query(vector=np.zeros(1, np.float32), roles=(0,), k=2)
    swapped = SearchResult(hits=[(0.0, 0), (1.0 + gap, 2)], path="batched")
    check.check([q], [swapped])
    assert bool(check.failures) != tie
    leak = SearchResult(hits=[(0.0, 0), (0.25, 3)], path="batched")
    check.check([q], [leak])
    assert any("unauthorized" in f for f in check.failures)
