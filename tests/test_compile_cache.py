"""Placement of JAX's persistent compilation cache (launch/compile_cache.py).

Each case runs in a fresh interpreter: the cache is process-wide JAX
configuration, and the test process must keep its own.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
counts = {"requests": 0, "hits": 0}
def on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        counts["requests"] += 1
def on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        counts["hits"] += 1
jax.monitoring.register_event_duration_secs_listener(on_duration)
jax.monitoring.register_event_listener(on_event)
path = use_compile_cache()
if COMPILE:
    jax.jit(lambda x: (x * 3.0).sum())(jnp.arange(7.0)).block_until_ready()
cfg = jax.config
print(json.dumps(dict(counts, path=path,
                      config=cfg.jax_compilation_cache_dir,
                      min_s=cfg.jax_persistent_cache_min_compile_time_secs)))
"""


def _probe(compile_: bool, cache_dir=None):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    r = subprocess.run(
        [sys.executable, "-c", f"COMPILE = {compile_}\n" + PROBE], env=env,
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_dir_holds_every_compile_and_serves_the_next_run(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX places the cache there, every
    compile (however short) is kept, and a second process compiles
    nothing fresh."""
    from repro.launch.compile_cache import DEFAULT_DIR
    before = sorted(os.listdir(DEFAULT_DIR)) if os.path.isdir(DEFAULT_DIR) \
        else None
    first = _probe(True, tmp_path)
    assert first["path"] == first["config"] == str(tmp_path)
    assert first["min_s"] == 0.0
    assert first["requests"] > 0 and first["hits"] == 0
    assert len(os.listdir(tmp_path)) >= first["requests"]
    second = _probe(True, tmp_path)
    assert second["requests"] == first["requests"]
    assert second["hits"] == second["requests"]
    after = sorted(os.listdir(DEFAULT_DIR)) if os.path.isdir(DEFAULT_DIR) \
        else None
    assert after == before          # nothing landed in the default dir


def test_default_dir_is_fixed_inside_the_checkout_and_ignored():
    out = _probe(False)
    assert out["path"] == out["config"] == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
