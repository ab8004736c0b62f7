"""End-to-end rehearsal on the CPU at the tiny size: each traffic mix runs
for a second through the whole harness and prints a well-formed result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import runner, spec

from .helpers import BIG_SEED, TEST_MIXES, tiny_cell

MIXES = sorted([f[:-5] for f in os.listdir(os.path.join(spec.BENCH, "traffic"))
                if f.endswith(".json")] + list(TEST_MIXES))


def check_result(res, trace):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert "breakdown" in res
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    json.dumps(res)


@pytest.mark.parametrize("mix", MIXES)
def test_each_mix_runs_end_to_end(mix):
    code, res = runner.run_cell(tiny_cell(mix), BIG_SEED, 1.0, False,
                                platforms=("cpu",))
    assert code == 0
    check_result(res, trace=False)
    assert set(res["metrics"]) == {"qps", "setup_s"}


def test_traced_run_reports_layers():
    code, res = runner.run_cell(tiny_cell("closed128"), 7, 1.0, True,
                                platforms=("cpu",))
    assert code == 0
    check_result(res, trace=True)
    assert {"queue_ms", "p99_ms", "launch_ms", "engine_host_ms",
            "flush_max_ms", "storage_amp"} <= set(res["metrics"])
    # the CPU has no TPU plane: device metrics are left out, never 0
    assert "l2topk_roofline" not in res["metrics"]
    assert "device_idle" not in res["metrics"]


def _cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.closed128",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_the_cpu():
    out = _cli(spec.ROOT, {})
    assert out.returncode != 0
    assert "Nothing was run" in out.stderr
    assert not out.stdout.strip().endswith("}")


def test_cli_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(str(tmp_path), {})
    assert out.returncode != 0
    assert not out.stdout.strip()
