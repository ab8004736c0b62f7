"""Device meshes: the training pod mesh and the retrieval serving mesh.

Two mesh flavours live here, both constructed by FUNCTIONS (never
module-level constants) so importing this module never touches jax device
state:

* **Training / dry-run meshes** (``make_production_mesh`` / ``make_mesh``):
  ``jax.sharding.Mesh`` objects for the LM side.  Single pod: 16x16 = 256
  chips (data, model); multi-pod: 2 pods x 256 = 512 chips
  (pod, data, model) — the "pod" axis is the cross-pod data-parallel
  dimension (DCN-connected in a real deployment).

* **Retrieval serving mesh** (:class:`DeviceMesh`): an ordered tuple of
  addressable devices over which :class:`~repro.core.sharded.ShardedVectorStore`
  places lattice-node shards (DESIGN.md §Sharded Execution).  Lattice nodes
  are disjoint, so retrieval needs no named mesh axes or collectives — each
  node shard is pinned to one device with ``jax.device_put`` and scored by an
  independent ``l2_topk`` launch; partial top-k results merge on the host.

  A :class:`DeviceMesh` may be *virtual*: when more slots are requested than
  physical devices exist, devices repeat round-robin.  Placement, per-slot
  executors, and the merge logic are identical either way, which is how the
  sharded parity suite runs at mesh sizes {1, 2, 4} on a single-device CPU
  container.  True multi-device CPU runs force
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the CI leg does);
  on TPU, ``jax.devices()`` are the real chips.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Full-pod training mesh: (16, 16) single pod or (2, 16, 16) dual pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))


def _auto_axes(n: int):
    """Auto axis types: the sharding rules place arrays with
    ``with_sharding_constraint``, which Explicit axes (JAX's default for
    ``make_mesh``) refuse."""
    return (jax.sharding.AxisType.Auto,) * n


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary mesh for tests / small dry-runs (e.g. (2,4) on 8 devices)."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))


# --------------------------------------------------------------------------
# Retrieval serving mesh (DESIGN.md §Sharded Execution)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DeviceMesh:
    """Ordered device slots for sharded lattice execution.

    ``devices[i]`` is the jax device behind slot ``i``.  Slots — not
    physical devices — are the placement and concurrency unit: the sharded
    store keeps one single-worker executor per slot (a "stream"), so two
    slots backed by the same physical device still serialize their kernel
    launches while distinct devices run concurrently.

    Use :meth:`host` to build one; ``DeviceMesh.host(1)`` is the degenerate
    mesh every single-device path routes through unchanged.
    """

    devices: Tuple[object, ...]          # jax.Device slots, possibly repeated

    def __post_init__(self):
        assert len(self.devices) >= 1, "a mesh needs at least one device slot"

    @property
    def size(self) -> int:
        """Number of device slots (the placement fan-out)."""
        return len(self.devices)

    @property
    def n_physical(self) -> int:
        """Number of distinct physical devices behind the slots."""
        return len({id(d) for d in self.devices})

    @property
    def is_virtual(self) -> bool:
        """True when slots outnumber physical devices (devices repeat)."""
        return self.size > self.n_physical

    def __len__(self) -> int:
        return self.size

    def __iter__(self):
        return iter(self.devices)

    def __getitem__(self, i: int):
        return self.devices[i]

    @classmethod
    def host(cls, size: Optional[int] = None,
             devices: Optional[Sequence[object]] = None) -> "DeviceMesh":
        """Mesh over this process's addressable devices.

        ``size=None`` takes every available device.  ``size`` larger than
        the physical device count cycles devices round-robin into virtual
        slots (placement/merge logic identical; no physical parallelism).
        Force real CPU multi-device with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
        first jax import.
        """
        avail: List[object] = list(devices if devices is not None
                                   else jax.devices())
        assert avail, "no jax devices available"
        if size is None:
            size = len(avail)
        assert size >= 1, size
        slots = tuple(avail[i % len(avail)] for i in range(size))
        return cls(devices=slots)

    def describe(self) -> str:
        """One-line human summary (exp18 report header, REPL debugging)."""
        kinds = {}
        for d in self.devices:
            kinds[str(getattr(d, "platform", d))] = \
                kinds.get(str(getattr(d, "platform", d)), 0) + 1
        plat = "+".join(f"{n}x{p}" for p, n in sorted(kinds.items()))
        tag = " virtual" if self.is_virtual else ""
        return f"DeviceMesh(size={self.size}, physical={self.n_physical}, " \
               f"{plat}{tag})"


def device_mesh(size: Optional[int] = None) -> DeviceMesh:
    """Convenience wrapper: ``DeviceMesh.host(size)``."""
    return DeviceMesh.host(size)
