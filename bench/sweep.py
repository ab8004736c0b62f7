#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's runs over many
seeds, and the control's, in one process.

    python3 bench/sweep.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <n> ...]

For each seed it makes a whole run of the cell (``runner.run_cell``, with a
window of ``--seconds``) and prints its checks; the programs compiled for
the first seed serve the others.  For each control seed it draws the same
corpus and query pool, puts the bfloat16 control (``harness/control.py``)
in the program's place for a sample of the pool as large as a run's, and
prints the same checks.  The last line is a JSON summary: per number, the
largest reading of the program and the smallest of the control.  Like
``run.py`` it measures only on a TPU.  The benchmark's runs never run it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_checks(cell, seed):
    from bench.harness import control, corpus, reference
    cfg, tr = cell.config, cell.traffic
    data = corpus.draw_cell(cfg, tr, seed)
    pick = corpus.check_pick(seed, len(data.pool), tr["check_sample"])
    qs = [data.pool[i] for i in pick]
    ref = reference.Reference(
        data.vectors, data.policy.allowed,
        None if data.attrs is None else data.attrs.eligible)
    return reference.compare(ref, qs, control.bf16_answers(ref, qs),
                             cfg["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench.harness.runner import run_cell
    from bench.harness.spec import load_cell
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 1
    cell = load_cell(args.workload, ROOT)
    program, ctrl = {}, {}
    for seed in args.seeds:
        t = time.perf_counter()
        code, res = run_cell(cell, seed, args.seconds, False, root=ROOT)
        if res is None:
            return code
        vals = {n: c["value"] for n, c in res["checks"].items()}
        print(f"SWEEP program seed={seed} correct={res['correct']} "
              f"{json.dumps(vals)} metrics="
              f"{json.dumps({n: m['value'] for n, m in res['metrics'].items()})}"
              f" ({time.perf_counter() - t:.1f} s)", flush=True)
        for n, v in vals.items():
            program[n] = max(program.get(n, v), v)
    for seed in args.control_seeds:
        t = time.perf_counter()
        vals = {n: c["value"] for n, c in control_checks(cell, seed).items()}
        print(f"SWEEP control seed={seed} {json.dumps(vals)} "
              f"({time.perf_counter() - t:.1f} s)", flush=True)
        for n, v in vals.items():
            ctrl[n] = min(ctrl.get(n, v), v)
    print(json.dumps({"workload": args.workload, "program_max": program,
                      "control_min": ctrl}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
