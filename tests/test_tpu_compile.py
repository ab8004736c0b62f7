"""Deviceless TPU compiles of the ``l2_topk`` kernel at real widths.

The TPU compiler is installed beside JAX, so the kernel is lowered and
compiled for a described v5e chip that is not attached: what Mosaic refuses
(an op it cannot lower, a block not aligned to the tiling, more VMEM than a
kernel may use) fails here, on CPU, before any chip run.  Nothing executes,
so these tests say nothing about results or times.

The topology is described only inside a fixture: only one process at a
time may load the TPU library, and pytest-xdist workers all import this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.l2_topk.kernel import l2_topk_pallas

B, N, K = 64, 65_536, 10


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a deviceless compile is written to the persistent cache but cannot be
    # read back without a chip; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("p", [0, 2], ids=lambda p: f"P{p}")
@pytest.mark.parametrize("w", [1, 8], ids=lambda w: f"W{w}")
@pytest.mark.parametrize("d", [128, 768], ids=lambda d: f"d{d}")
def test_l2_topk_compiles_for_v5e(one_chip, d, w, p):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [spec((B, d), jnp.float32), spec((N, d), jnp.float32),
            spec((w, N), jnp.uint32), spec((B, w), jnp.uint32),
            spec((B, 1), jnp.float32)]
    pred = {}
    if p:
        pred = dict(attr_words=spec((p, N), jnp.uint32),
                    require=spec((B, p), jnp.uint32),
                    forbid=spec((B, p), jnp.uint32))

    def launch(q, db, auth, mask, bound, **kw):
        return l2_topk_pallas(q, db, auth, mask, bound, N, K,
                              interpret=False, **kw)

    compiled = jax.jit(launch).lower(*args, **pred).compile()
    assert "tpu_custom_call" in compiled.as_text()
