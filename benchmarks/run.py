"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Default sizes finish on the CPU
container; ``--full`` scales toward the paper's setup; ``--only exp05``
runs a single experiment.
"""
from __future__ import annotations

import argparse
import sys
import time

from . import experiments as E
from . import kernels as K
from .common import BenchConfig, MethodSuite, dataset, emit, CSV_ROWS


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="run a single experiment, e.g. exp05 or kernels")
    ap.add_argument("--out", default=None)
    ap.add_argument("--json", default=None,
                    help="also write a structured JSON report (CI artifact)")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    print(f"# compile cache: {use_compile_cache()}", file=sys.stderr)

    bc = BenchConfig()
    if args.full:
        bc = BenchConfig(n_vectors=100_000, dim=64, n_roles=32,
                         n_permissions=120, n_queries=100, n_runs=10,
                         lam=2900, M=16, efc=100)

    t0 = time.time()
    want = args.only

    def go(name, fn):
        if want and want not in name:
            return
        print(f"# --- {name} ---", file=sys.stderr)
        fn()

    # construction experiments (cost-model only — fast)
    go("exp01", lambda: E.exp01_build_time(bc))
    go("exp02", lambda: E.exp02_indexed_vs_leftover(bc))
    go("exp03", lambda: E.exp03_n_indices(bc))
    go("exp04", lambda: E.exp04_desired_vs_achieved_sa(bc))
    go("exp05", lambda: E.exp05_qa_vs_sa(bc))
    go("exp07", lambda: E.exp07_indices_per_query(bc))

    # query experiments sharing one engine suite
    suite = None
    needs_suite = [n for n in ("exp06", "exp10", "exp13", "exp14")
                   if (not want or want in n)]
    if needs_suite:
        print("# building method suite (HNSW engines)...", file=sys.stderr)
        suite = MethodSuite(bc, dataset(bc))
        emit("suite_build/veda", suite.t_veda * 1e6, "partition_s")
        emit("suite_build/effveda", suite.t_effveda * 1e6, "partition_s")
        emit("suite_build/sieve", suite.t_sieve * 1e6, "partition_s")
        emit("suite_build/honeybee", suite.t_honeybee * 1e6, "partition_s")
    go("exp06", lambda: E.exp06_purity(bc, suite))
    go("exp08", lambda: E.exp08_lambda_sensitivity(bc))
    go("exp09", lambda: E.exp09_coordinated_effect(bc))
    go("exp10", lambda: E.exp10_efs_sweep(bc, suite))
    go("exp11", lambda: E.exp11_qps_recall_datasets(bc))
    go("exp12", lambda: E.exp12_sensitivity(bc))
    go("exp13", lambda: E.exp13_weighted_workload(bc, suite))
    go("exp14", lambda: E.exp14_multirole(bc, suite))
    go("exp15", lambda: E.exp15_batched_throughput(bc))
    go("exp16", lambda: E.exp16_continuous_batching(bc))
    go("exp17", lambda: E.exp17_role_scaling(bc))
    go("exp18", lambda: E.exp18_sharded_scaling(bc))
    go("exp19", lambda: E.exp19_sustained_churn(bc))
    go("exp20", lambda: E.exp20_slo_serving(bc))
    go("exp21", lambda: E.exp21_drift_reoptimization(bc))
    go("exp22", lambda: E.exp22_filtered_selectivity(bc))

    go("kernels", K.run_all)

    elapsed = time.time() - t0
    print(f"# done in {elapsed:.0f}s, {len(CSV_ROWS)} rows",
          file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(CSV_ROWS) + "\n")
    if args.json:
        import dataclasses
        import json
        rows = []
        for row in CSV_ROWS:
            name, us, derived = row.split(",", 2)
            rec = {"name": name, "us_per_call": float(us)}
            for kv in filter(None, derived.split(";")):
                key, _, val = kv.partition("=")
                try:
                    rec[key] = float(val)
                except ValueError:
                    rec[key] = val
            rows.append(rec)
        with open(args.json, "w") as f:
            json.dump({"config": dataclasses.asdict(bc),
                       "only": args.only, "elapsed_s": round(elapsed, 2),
                       "rows": rows}, f, indent=2)
        print(f"# json report → {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
