"""Quickstart: build an access-aware index and run authorized queries.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import (HNSWCostModel, SearchStats, build_effveda,
                        build_veda, build_vector_storage, coordinated_search,
                        exact_factory, generate_policy, metrics)

# 1. a dataset where every vector carries a role combination -----------------
rng = np.random.default_rng(0)
N, DIM, ROLES = 6000, 32, 10
vectors = rng.standard_normal((N, DIM)).astype(np.float32)
policy = generate_policy(N, n_roles=ROLES, n_permissions=30, seed=0)
print(f"dataset: {N} vectors, {ROLES} roles, "
      f"{policy.n_blocks} distinct permission sets")

# 2. optimize the access-aware lattice under a storage budget ----------------
cm = HNSWCostModel(lam_threshold=400)          # calibrated via Appendix B
result = build_effveda(policy, cm, beta=1.1, k=10)
print(f"EffVEDA: SA={result.sa:.3f} (budget 1.1), "
      f"{len(result.lattice.nodes)} indexable nodes, "
      f"{len(result.leftovers)} leftover blocks, "
      f"QA={metrics.query_amplification(result, cm, 10):.3f}")

# 3. materialize engines + query with coordinated search ---------------------
store = build_vector_storage(result, vectors, engine_factory=exact_factory())
stats = SearchStats()
role = 3
q = vectors[policy.d_of_role(role)[0]] + 0.05 * rng.standard_normal(DIM).astype(np.float32)
results = coordinated_search(store, q, role, k=10, efs=50, stats=stats)
print(f"top-10 for role {role}: {[vid for _, vid in results]}")
assert all(policy.authorized_mask(role)[vid] for _, vid in results)
print(f"all results authorized ✓  (purity={stats.purity:.2f}, "
      f"indices visited={stats.indices_visited})")

# 4. the same query as a different role sees different data ------------------
other = coordinated_search(store, q, (role + 1) % ROLES, k=10, efs=50)
print(f"role {(role + 1) % ROLES} sees: {[vid for _, vid in other]}")

# 5. the typed entry point: one batch, mixed roles and ks --------------------
from repro.core import Query
batch = [Query(vector=q, roles=(role,), k=5),
         Query(vector=q, roles=(role, (role + 1) % ROLES), k=3)]  # union
for query, res in zip(batch, store.search(batch)):
    print(f"roles {query.roles} k={query.k} -> {res.ids}  (path={res.path})")

# 6. the same store on the TPU kernel engine, sharded across a mesh ----------
# (the Pallas kernel is interpreted on CPU and compiled on a TPU backend;
#  see DESIGN.md §3 and §Sharded Execution)
from repro.ann.scorescan import scorescan_factory
from repro.launch.mesh import DeviceMesh
kstore = build_vector_storage(result, vectors,
                              engine_factory=scorescan_factory(policy))
sharded = kstore.sharded(DeviceMesh.host(2))   # 2 slots (virtual on 1 device)
sres = sharded.search(batch)
assert [r.ids for r in sres] == [r.ids for r in store.search(batch)]
print(f"sharded mesh: {sharded.mesh.describe()}, "
      f"placement imbalance {sharded.placement.imbalance():.2f}, "
      f"same authorized results ✓")
sharded.close()
