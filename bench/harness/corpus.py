"""The benchmark's own data: corpus, access policy and queries.

Everything here is a copy owned by the benchmark, so that a change to the
program cannot change what it is measured on:

- the clustered-Gaussian corpus of the repository's retrieval generator
  (``make_retrieval_dataset``), drawn on the device from ``--seed`` in
  float32, one fixed-size chunk per call of one compiled program;
- the shifted-Zipf role-combination and block-assignment generator of the
  paper's section 7.1 (``generate_policy``), drawn from the configuration's
  fixed ``policy_seed``: the permission structure is part of the deployment,
  so every run seed serves the same lattice, node sizes and compiled shapes,
  over different vectors and traffic;
- query vectors as the section 7.1 generator draws them: a point of the
  querying role's own data plus Gaussian noise.

Nothing in this module imports the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, FrozenSet, List, Sequence, Tuple

import numpy as np

CHUNK_ROWS = 65536          # rows per device call of the corpus generator


def seed_words(seed: int) -> Tuple[int, int]:
    """A seed of up to 64 bits as two uint32 words (JAX keys take 32)."""
    s = int(seed) % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of a run seed."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


# ------------------------------------------------------------------ policy
@dataclasses.dataclass(frozen=True)
class PolicyDraw:
    """Raw access assignment: each vector's block, each block's roles."""

    n_roles: int
    block_roles: Tuple[FrozenSet[int], ...]
    assign: np.ndarray                  # (N,) block index per vector
    order: np.ndarray                   # vector ids sorted by block
    starts: np.ndarray                  # (n_blocks + 1,) offsets into order

    def members(self, b: int) -> np.ndarray:
        return self.order[self.starts[b]:self.starts[b + 1]]

    def role_blocks(self, r: int) -> List[int]:
        return [b for b, tau in enumerate(self.block_roles) if r in tau]

    def role_ids(self, r: int) -> np.ndarray:
        """D(r): every vector id role ``r`` may read, ascending by block."""
        parts = [self.members(b) for b in self.role_blocks(r)]
        return np.concatenate(parts) if parts else np.empty(0, np.int64)

    def allowed(self, roles: Sequence[int]) -> np.ndarray:
        """(N,) bool: vectors readable under the union of ``roles``."""
        want = set(int(r) for r in roles)
        ok_block = np.array([bool(tau & want) for tau in self.block_roles])
        return ok_block[self.assign]


def _shifted_zipf(n: int, s: float, alpha: float) -> np.ndarray:
    w = (np.arange(1, n + 1, dtype=np.float64) + s) ** (-alpha)
    return w / w.sum()


def draw_policy(n_vectors: int, n_roles: int, n_permissions: int,
                block_zipf: Sequence[float], perm_zipf: Sequence[float],
                max_roles_per_perm: int, seed: int) -> PolicyDraw:
    """The section 7.1 policy generator (same draws, same order as the
    repository's ``generate_policy``); members are found by one sort
    instead of one scan per block."""
    rng = np.random.default_rng(seed)
    perm_weights = _shifted_zipf(n_roles, *perm_zipf)
    combos: List[FrozenSet[int]] = []
    seen = set()
    for r in range(min(n_roles, n_permissions)):
        combos.append(frozenset([r]))
        seen.add(frozenset([r]))
    attempts = 0
    while len(combos) < n_permissions and attempts < 50 * n_permissions:
        attempts += 1
        size = int(rng.integers(1, min(max_roles_per_perm, n_roles) + 1))
        tau = frozenset(
            int(x) for x in
            rng.choice(n_roles, size=size, replace=False, p=perm_weights))
        if tau not in seen:
            seen.add(tau)
            combos.append(tau)
    block_w = _shifted_zipf(len(combos), *block_zipf)
    order = rng.permutation(len(combos))
    assign = rng.choice(len(combos), size=n_vectors,
                        p=block_w[order][np.argsort(order)])
    counts = np.bincount(assign, minlength=len(combos))
    spare = np.flatnonzero(counts > 1)
    for b in np.flatnonzero(counts == 0):
        donor = spare[rng.integers(len(spare))]
        victim = np.flatnonzero(assign == donor)[0]
        assign[victim] = b
        counts = np.bincount(assign, minlength=len(combos))
        spare = np.flatnonzero(counts > 1)
    by_block = np.argsort(assign, kind="stable").astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return PolicyDraw(n_roles=n_roles, block_roles=tuple(combos),
                      assign=assign.astype(np.int64), order=by_block,
                      starts=starts)


# ------------------------------------------------------------------ vectors
@functools.lru_cache(maxsize=None)
def _chunk_program(rows: int, dim: int, n_clusters: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chunk(key, centers, index):
        k = jax.random.fold_in(key, index)
        ka, kn = jax.random.split(k)
        assign = jax.random.randint(ka, (rows,), 0, n_clusters)
        return centers[assign] + jax.random.normal(kn, (rows, dim),
                                                   jnp.float32)
    return chunk


def draw_vectors(seed: int, n_vectors: int, dim: int, n_clusters: int,
                 center_scale: float) -> np.ndarray:
    """(N, d) float32 clustered Gaussian corpus, drawn on the default
    device chunk by chunk and gathered once into host memory."""
    import jax
    import jax.numpy as jnp
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), np.uint32(lo)), np.uint32(hi))
    kc, kv = jax.random.split(key)
    centers = jax.random.normal(kc, (n_clusters, dim), jnp.float32) \
        * np.float32(center_scale)
    rows = min(CHUNK_ROWS, n_vectors)
    program = _chunk_program(rows, dim, n_clusters)
    out = np.empty((n_vectors, dim), np.float32)
    pending = None
    for i, at in enumerate(range(0, n_vectors, rows)):
        block = program(kv, centers, np.uint32(i))    # dispatch the next
        if pending is not None:
            copy_into(out, *pending)
        pending = (at, block)
    copy_into(out, *pending)
    return out


def copy_into(out: np.ndarray, at: int, block) -> None:
    n = min(len(block), len(out) - at)
    out[at:at + n] = np.asarray(block)[:n]


# ------------------------------------------------------------------ queries
@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One request as the benchmark draws it (the program's ``Query`` is
    built from it by the system module)."""

    vector: np.ndarray
    roles: Tuple[int, ...]
    k: int


def draw_queries(seed: int, n: int, vectors: np.ndarray, policy: PolicyDraw,
                 k: int, union_share: float,
                 noise: float) -> List[QuerySpec]:
    """``n`` queries: a uniform querying role and a second role with
    probability ``union_share``; the vector is a point of the first role's
    own data plus ``noise`` times a standard normal."""
    rng = host_rng(seed, 3)
    role_ids: Dict[int, np.ndarray] = {}
    dim = vectors.shape[1]
    out = []
    for _ in range(n):
        r = int(rng.integers(policy.n_roles))
        roles = (r,)
        if rng.random() < union_share:
            roles += (int((r + 1 + rng.integers(policy.n_roles - 1))
                          % policy.n_roles),)
        if r not in role_ids:
            role_ids[r] = policy.role_ids(r)
        ids = role_ids[r]
        base = vectors[ids[rng.integers(len(ids))]]
        vec = (base + noise * rng.standard_normal(dim)).astype(np.float32)
        out.append(QuerySpec(vector=vec, roles=roles, k=k))
    return out

