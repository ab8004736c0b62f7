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
  querying role's own data plus Gaussian noise;
- where the configuration declares ``predicates``, each row's raw
  attributes (a value index per tag field, drawn by the field's Zipf
  weights; a float per range field, uniform on its range), and where the
  traffic mix declares ``filtered_share``, a ``where`` clause on each query
  with that probability, drawn from the mix's ``where_pool`` of templates.

Each draw of a run seed has a numpy stream of its own (``host_rng``): 3
the queries, 5 the sample that is checked, 6 the queries' clauses, 7 the
rows' attributes; the vectors are drawn on the device.  A configuration
without ``predicates`` and a mix without ``filtered_share`` draw exactly
what they drew before the predicate plane.

Nothing in this module imports the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

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


# --------------------------------------------------------------- attributes
Atom = Tuple[str, str, object]     # (op, field, tag index | range edge)
TAG_OPS = ("has", "lacks")
RANGE_OPS = ("ge", "lt")


@dataclasses.dataclass(frozen=True)
class Attributes:
    """Each row's raw attribute values: a value index per tag field and a
    float per range field, under the configuration's ``predicates``."""

    n_rows: int
    tags: Dict[str, np.ndarray]         # field -> (N,) value index
    ranges: Dict[str, np.ndarray]       # field -> (N,) float64 value

    def eligible(self, where: Sequence[Atom]) -> np.ndarray:
        """(N,) bool: the rows that satisfy every atom of ``where``:
        ``has``/``lacks`` a tag value, ``ge``/``lt`` a range edge."""
        ok = np.ones(self.n_rows, bool)
        for op, field, value in where:
            if op == "has":
                ok &= self.tags[field] == value
            elif op == "lacks":
                ok &= self.tags[field] != value
            elif op == "ge":
                ok &= self.ranges[field] >= value
            elif op == "lt":
                ok &= self.ranges[field] < value
            else:
                raise ValueError(f"unknown where op {op!r}")
        return ok


def tag_weights(spec: Dict) -> np.ndarray:
    """A tag field's value weights: Zipf with exponent ``zipf`` over
    ``values`` values, value 0 the most frequent."""
    w = np.arange(1, spec["values"] + 1, dtype=np.float64) ** -spec["zipf"]
    return w / w.sum()


def draw_attributes(seed: int, n_vectors: int, predicates: Dict
                    ) -> Attributes:
    """The rows' raw attributes under ``predicates`` (``{"tags": {field:
    {"values", "zipf"}}, "ranges": {field: {"low", "high", "edges"}}}``),
    fields in declaration order, on stream 7 of the run seed."""
    rng = host_rng(seed, 7)
    tags = {f: rng.choice(spec["values"], size=n_vectors,
                          p=tag_weights(spec)).astype(np.int64)
            for f, spec in predicates.get("tags", {}).items()}
    ranges = {f: rng.uniform(spec["low"], spec["high"], n_vectors)
              for f, spec in predicates.get("ranges", {}).items()}
    return Attributes(n_rows=n_vectors, tags=tags, ranges=ranges)


# ------------------------------------------------------------------ queries
@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One request as the benchmark draws it (the program's ``Query`` is
    built from it by the system module)."""

    vector: np.ndarray
    roles: Tuple[int, ...]
    k: int
    where: Optional[Tuple[Atom, ...]] = None


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


def fill_clause(template: Sequence, predicates: Dict,
                rng: np.random.Generator) -> Tuple[Atom, ...]:
    """One clause from a template of ``[op, field, placeholder]`` atoms: a
    tag atom takes a value drawn by its field's weights; the range atoms
    of one field take distinct edges, ascending in atom order, drawn
    uniformly from the edges above the field's ``low`` (``ge low`` filters
    nothing and ``lt low`` admits nothing)."""
    values: Dict[int, object] = {}
    by_field: Dict[str, List[int]] = {}
    for j, (op, field, _) in enumerate(template):
        if op in TAG_OPS:
            spec = predicates["tags"][field]
            values[j] = int(rng.choice(spec["values"], p=tag_weights(spec)))
        elif op in RANGE_OPS:
            by_field.setdefault(field, []).append(j)
        else:
            raise ValueError(f"unknown where op {op!r} in {template!r}")
    for field, atoms in by_field.items():
        spec = predicates["ranges"][field]
        edges = [float(e) for e in spec["edges"] if e > spec["low"]]
        pick = np.sort(rng.choice(len(edges), len(atoms), replace=False))
        for j, e in zip(atoms, pick):
            values[j] = edges[e]
    return tuple((op, field, values[j])
                 for j, (op, field, _) in enumerate(template))


def add_filters(seed: int, pool: List[QuerySpec], share: float,
                templates: Sequence, predicates: Dict) -> List[QuerySpec]:
    """Each query takes a clause with probability ``share``, one coin per
    query; the clause comes from one of the ``templates``, drawn with equal
    weights.  All on stream 6 of the run seed."""
    rng = host_rng(seed, 6)
    out = []
    for q in pool:
        if rng.random() < share:
            t = templates[int(rng.integers(len(templates)))]
            q = dataclasses.replace(q, where=fill_clause(t, predicates, rng))
        out.append(q)
    return out


# a kind of flush that the mix makes with less probability is not warmed
NEVER = 1e-9


def flush_kinds(share: float, batch: int) -> Tuple[bool, ...]:
    """Whether a flush of ``batch`` queries holds a filtered query: the
    kinds (False: none, True: at least one) that the mix makes with more
    probability than ``NEVER``.  With one coin per query (``add_filters``)
    a flush holds none with probability ``(1 - share) ** batch``."""
    bare = (1.0 - share) ** batch
    return tuple(f for f, p in ((False, bare), (True, 1.0 - bare))
                 if p > NEVER)


# ------------------------------------------------------------------ a cell
@dataclasses.dataclass
class CellData:
    """Everything one run of a cell draws from its seed."""

    policy: PolicyDraw
    vectors: np.ndarray
    attrs: Optional[Attributes]
    pool: List[QuerySpec]


def draw_cell(config: Dict, traffic: Dict, seed: int) -> CellData:
    """The policy, the corpus, the rows' attributes (where the
    configuration declares ``predicates``) and the query pool (filtered
    where the mix declares ``filtered_share``)."""
    cfg, tr = config, traffic
    policy = draw_policy(cfg["n_vectors"], cfg["n_roles"],
                         cfg["n_permissions"], cfg["block_zipf"],
                         cfg["perm_zipf"], cfg["max_roles_per_perm"],
                         cfg["policy_seed"])
    vectors = draw_vectors(seed, cfg["n_vectors"], cfg["dim"],
                           cfg["n_clusters"], cfg["center_scale"])
    attrs = None
    if "predicates" in cfg:
        attrs = draw_attributes(seed, cfg["n_vectors"], cfg["predicates"])
    pool = draw_queries(seed, tr["pool"], vectors, policy, tr["k"],
                        tr["union_share"], cfg["query_noise"])
    if tr.get("filtered_share", 0.0):
        if attrs is None:
            raise ValueError("a filtered traffic mix needs a configuration "
                             "that declares predicates")
        pool = add_filters(seed, pool, tr["filtered_share"],
                           tr["where_pool"], cfg["predicates"])
    return CellData(policy=policy, vectors=vectors, attrs=attrs, pool=pool)


def check_pick(seed: int, n_requests: int, n_sample: int) -> np.ndarray:
    """The window requests held to the reference: ``n_sample`` of them
    (at most all), drawn on stream 5 of the run seed, ascending."""
    rng = host_rng(seed, 5)
    return np.sort(rng.choice(n_requests, min(n_sample, n_requests),
                              replace=False))
