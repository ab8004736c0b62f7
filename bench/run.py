#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine whose JAX backend is a TPU.
``BENCHMARK.json`` names the cell's configuration, traffic mix and metrics;
``bench/harness/runner.py`` says what one run does.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics with ``--trace 0``, the per-layer ones
with ``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and
last the ``checks`` that decided ``correct``, each with its limit.

Without a TPU, without as many chips as the cell asks for, or without the
program beside the benchmark (``src/repro``), it exits nonzero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program at {os.path.join(ROOT, 'src', 'repro')}; "
              f"nothing was run", file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness.runner import run_cell
    from bench.harness.spec import load_cell
    cell = load_cell(args.workload, ROOT)
    code, result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            t_start=T_START, root=ROOT)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
