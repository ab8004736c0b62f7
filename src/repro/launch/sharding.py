"""Logical-axis sharding rules (MaxText-style) for the multi-pod mesh,
plus the row-placement utilities of the retrieval serving mesh.

Model code annotates tensors with *logical* axis names; a :class:`Rules`
object maps logical names to mesh axes per shape profile and applies
``with_sharding_constraint``.  Divisibility is checked at constraint time —
an axis that does not divide the dimension is dropped (replicated), which is
how e.g. minicpm's 36 heads degrade gracefully on a 16-way model axis.

Retrieval sharding is much simpler than the training rules: lattice nodes
are disjoint, so a node shard is just a contiguous row range pinned to one
device (``l2_topk.prepare_node`` with that device), and row-splitting a
node across devices is an even partition of its row count
(:func:`even_row_splits`) — no named axes, no collectives (DESIGN.md
§Sharded Execution)."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

# Default logical→mesh rules. "seq" → "model" is Megatron-style sequence
# parallelism for the residual stream; attention/MLP internals re-shard to
# heads/ff TP automatically under these output constraints.
TRAIN_RULES: Dict[str, MeshAxes] = {
    "batch": ("pod", "data"),
    "seq": "model",
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_groups": ("pod", "data"),
    "moe_all": ("pod", "data", "model"),
    "capacity": None,
    "layers": None,
    "fsdp": ("pod", "data"),          # weight sharding (FSDP over data axes)
    "state": None,
    "kv_seq": "model",
}

DECODE_RULES = dict(TRAIN_RULES)
DECODE_RULES.update({
    "seq": None,                      # one-token step: can't shard q seq
    "kv_seq": "model",               # KV cache sequence-sharded
})

LONG_DECODE_RULES = dict(DECODE_RULES)
LONG_DECODE_RULES.update({
    "batch": None,                    # batch=1
    "kv_seq": ("pod", "data", "model"),
})


@dataclasses.dataclass
class Rules:
    """Binds logical rules to a concrete mesh (or None → no-op for tests)."""

    mesh: Optional[Mesh]
    table: Dict[str, MeshAxes]

    def _axis_size(self, axes: MeshAxes) -> int:
        if axes is None or self.mesh is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        n = 1
        for a in axes:
            n *= self.mesh.shape.get(a, 1)
        return n

    def spec(self, names: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        """PartitionSpec for logical ``names``; drops non-dividing axes and
        axes already used by an earlier dimension."""
        used: set = set()
        parts = []
        for i, name in enumerate(names):
            axes = self.table.get(name) if name else None
            if axes is None:
                parts.append(None)
                continue
            t = (axes,) if isinstance(axes, str) else tuple(axes)
            t = tuple(a for a in t
                      if self.mesh is not None and a in self.mesh.shape
                      and a not in used)
            if not t:
                parts.append(None)
                continue
            if shape is not None:
                n = 1
                for a in t:
                    n *= self.mesh.shape[a]
                if shape[i] % n != 0:
                    # try prefixes before giving up (e.g. ("pod","data")→pod)
                    while t and shape[i] % n != 0:
                        n //= self.mesh.shape[t[-1]]
                        t = t[:-1]
                    if not t:
                        parts.append(None)
                        continue
            used.update(t)
            parts.append(t if len(t) > 1 else t[0])
        return P(*parts)

    def sharding(self, names: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(names, shape))

    def constrain(self, x: jax.Array,
                  names: Sequence[Optional[str]]) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(names, x.shape)))


def make_rules(mesh: Optional[Mesh], kind: str = "train") -> Rules:
    table = {"train": TRAIN_RULES, "prefill": TRAIN_RULES,
             "decode": DECODE_RULES, "long": LONG_DECODE_RULES}[kind]
    return Rules(mesh=mesh, table=dict(table))


NO_RULES = Rules(mesh=None, table={})


# --------------------------------------------------------------------------
# Retrieval serving-mesh placement (DESIGN.md §Sharded Execution)
# --------------------------------------------------------------------------
def even_row_splits(n: int, parts: int) -> List[Tuple[int, int]]:
    """Partition ``n`` rows into ``parts`` contiguous ``(lo, hi)`` ranges.

    Sizes differ by at most one row (the first ``n % parts`` ranges get the
    extra), and empty ranges are dropped — splitting 5 rows 4 ways yields
    ``[(0, 2), (2, 3), (3, 4), (4, 5)]``, splitting 2 rows 4 ways yields
    ``[(0, 1), (1, 2)]``.  The sharded store uses this to row-split lattice
    nodes larger than its split threshold across mesh slots.
    """
    assert n >= 0 and parts >= 1, (n, parts)
    parts = min(parts, n) or 1
    base, extra = divmod(n, parts)
    out: List[Tuple[int, int]] = []
    lo = 0
    for i in range(parts):
        hi = lo + base + (1 if i < extra else 0)
        if hi > lo:
            out.append((lo, hi))
        lo = hi
    return out


def tree_shardings(rules: Rules, axes_tree):
    """Map a pytree of logical-axis tuples to NamedShardings (or None)."""
    if rules.mesh is None:
        return None
    return jax.tree.map(
        lambda names: NamedSharding(rules.mesh, rules.spec(names)),
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))
