"""One run of one cell: set-up, the measured window, the check, the metrics.

Set-up (``setup_s``, from the start of the process): the corpus and the
policy are drawn, the program's store is built, every shape the cell's
traffic reaches is warmed through the engines' own protocol, ``warm_s``
seconds of the cell's own traffic run through a scheduler of their own,
and a full garbage collection ends it, so that the window does not inherit
a collection that set-up's allocations made due.  Then a fresh scheduler
serves the window, traced when asked.
After the window: the device's peak memory is read, the program's state is
freed, and a sample of the window's answers, drawn from the seed, is held
to the reference.  The result is one JSON object, printed last.
"""
from __future__ import annotations

import asyncio
import gc
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from . import corpus, reference, spec, system, traffic
from .compiles import CompileCounter
from .record import RunRecord
from .spans import FLUSH, SpanRecorder
from .trace import WINDOW, find_xplane, load


def log(msg: str) -> None:
    print(msg, flush=True)


def use_compile_cache(root: str) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    the fixed ``<checkout>/.jax_cache``; every compile is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


async def _serve(store, tr: Dict, make: Callable, seconds: float,
                 start: int) -> Tuple[traffic.Window, object]:
    sched = system.scheduler(store, tr)
    try:
        win = await traffic.closed_loop(sched, make, tr["clients"], seconds,
                                        start_index=start)
    finally:
        await sched.close()
    return win, sched.stats


def _flush_summary(spans, t0: float) -> str:
    s = [f for f in spans if f.name == FLUSH]
    if not s:
        return "flushes: none"
    longest = max(s, key=lambda f: f.seconds)
    return (f"flush_s: median={np.median([f.seconds for f in s]):.3f} "
            f"longest={longest.seconds:.3f} at +{longest.t0 - t0:.1f} s")


def _filter_summary(spans) -> str:
    s = [f for f in spans if f.name == FLUSH]
    mixed = sum(0 < f.filtered < f.rows for f in s)
    return (f"filtered: {sum(f.filtered for f in s)} of "
            f"{sum(f.rows for f in s)} queries; {mixed} of {len(s)} flushes "
            f"held filtered and unfiltered queries")


def _check_lines(checks: Dict[str, Dict[str, float]]) -> str:
    return "\n".join(f"check {n}: {c['value']!r} (limit {c['limit']!r})"
                     for n, c in checks.items())


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             platforms: Sequence[str] = ("tpu",),
             t_start: Optional[float] = None,
             root: str = spec.ROOT) -> Tuple[int, Optional[Dict]]:
    """Returns (exit code, result object or None)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cache = use_compile_cache(root)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform not in platforms:
        print(f"bench: JAX's default device is {dev.platform!r} "
              f"({dev.device_kind}); this benchmark measures only on "
              f"{'/'.join(platforms)}. Nothing was run.", file=sys.stderr)
        return 1, None
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}. Nothing was run.", file=sys.stderr)
        return 1, None
    log(f"compile cache: {cache}")
    cfg, tr = cell.config, cell.traffic
    compiles = CompileCounter()

    t = time.perf_counter()
    data = corpus.draw_cell(cfg, tr, seed)
    pool = data.pool
    t_data = time.perf_counter() - t

    t = time.perf_counter()
    built = system.build(cfg, data.vectors, data.policy, data.attrs)
    queries = [system.to_query(q) for q in pool]
    rec = SpanRecorder()
    system.instrument(built, rec)
    t_build = time.perf_counter() - t
    log(f"store: {system.describe(built)}")

    t = time.perf_counter()
    snap = compiles.snapshot()
    calls = system.warm(built, tr["k"], tr["max_batch"], cfg["dim"], seed,
                        corpus.flush_kinds(tr.get("filtered_share", 0.0),
                                           tr["max_batch"]))
    log(f"warm-up shapes: {calls} calls, {compiles.since(snap)}")
    snap = compiles.snapshot()

    def make(i: int):
        return queries[i % len(queries)]

    rec.on = True
    win, _ = asyncio.run(_serve(built.store, tr, make, tr["warm_s"],
                                len(queries) // 2))
    rec.on = False
    log(f"warm-up traffic: {len(win.requests)} requests in "
        f"{tr['warm_s']} s, {_flush_summary(rec.spans, win.t0)}, "
        f"{compiles.since(snap)}")
    rec.clear()
    t_gc = time.perf_counter()
    tracked = len(gc.get_objects())
    gc.collect()
    log(f"gc: full collection at the end of set-up, {tracked} objects "
        f"tracked, {time.perf_counter() - t_gc:.3f} s")
    t_warm = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f} data_s={t_data:.3f} build_s={t_build:.3f} "
        f"warm_s={t_warm:.3f}")

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    snap = compiles.snapshot()
    rec.on = True
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            win, stats = asyncio.run(_serve(built.store, tr, make, seconds,
                                            0))
    finally:
        rec.on = False
        if trace:
            jax.profiler.stop_trace()
    log(f"window: {len(win.requests)} requests in {seconds} s, "
        f"flushes={stats.batches_flushed} avg_batch={stats.avg_batch:.2f} "
        f"max_batch={stats.batch_size_max} "
        f"queue_depth_peak={stats.queue_depth_peak} paths={stats.paths}; "
        f"window {compiles.since(snap)}")
    log(f"window {_flush_summary(rec.spans, win.t0)}")
    if any(q.where for q in pool):
        log(f"window {_filter_summary(rec.spans)}")

    stats_mem = dev.memory_stats() or {}
    peak = int(stats_mem.get("peak_bytes_in_use", 0))
    storage_amp = float(built.store.sa())
    queue_ms = list(stats.queue_ms)
    tr_data = None
    if trace:
        path = find_xplane(log_dir)
        tr_data = load(path) if path else None
        shutil.rmtree(log_dir, ignore_errors=True)
    del built, stats
    gc.collect()

    t = time.perf_counter()
    answers = [system.to_answer(r.outcome) for r in win.requests]
    failed = sum(a is None for a in answers)
    pick = corpus.check_pick(seed, len(win.requests), tr["check_sample"])
    n_sample = len(pick)
    ref = reference.Reference(
        data.vectors, data.policy.allowed,
        None if data.attrs is None else data.attrs.eligible)
    checks = reference.compare(
        ref, [pool[win.requests[i].index % len(pool)] for i in pick],
        [answers[i] for i in pick], cfg["limits"])
    checks["missing"]["value"] = failed
    correct = reference.passed(checks)
    log(f"check: {n_sample} sampled answers of {len(answers)} "
        f"({time.perf_counter() - t:.3f} s)")

    record = RunRecord(cell=cell.name, config=cfg, traffic=tr,
                       device_kind=dev.device_kind, setup_s=setup_s,
                       window=win, spans=list(rec.spans), queue_ms=queue_ms,
                       storage_amp=storage_amp, trace=tr_data)
    metrics = spec.read_metrics(cell.per_layer if trace else cell.end_to_end,
                                record, root)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(win.requests),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace and tr_data is not None:
        device["busy_s"] = tr_data.busy_s()
        device["window_s"] = tr_data.window_s
        result["breakdown"] = tr_data.breakdown()
    result["checks"] = checks
    print(_check_lines(checks), file=sys.stderr, flush=True)
    return 0, result
