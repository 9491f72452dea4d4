"""Quickstart: color a graph with the paper's hybrid engine.

The graph comes from the dataset registry (DESIGN.md §8) and the run
goes through an execution *session* (DESIGN.md §9): the session owns the
compile cache, so the second request for the same spec x graph reuses
every prepared artifact instead of re-deriving it — the serving-path
behaviour, demonstrated by the cache stats below.

  PYTHONPATH=src python examples/quickstart.py
"""
from repro.exec import ExecutionSpec, default_session
from repro.graphs import get_dataset, validate_coloring

g = get_dataset("kron_g500-logn21_s", scale=0.05, layout="auto")
print(f"graph: {g.name}  nodes={g.n_nodes:,}  edges={g.n_edges:,}  "
      f"layout={g.layout.kind} (K={g.ell_width})")

session = default_session()          # the process-wide session
# the spec names everything static about the run; regime="host" is the
# host loop (one read-back an iteration), "outlined" the device-resident
# while_loop chunks, "dist" the sharded Pipe
spec = ExecutionSpec(regime="host", mode="hybrid", h=0.6)
print(f"regime: {spec.regime}")

result = session.run(spec, g)        # cold: prepares + compiles
check = validate_coloring(g, result.colors)

print(f"colors used : {result.n_colors}")
print(f"iterations  : {result.iterations}  (modes: {result.mode_trace})")
print(f"valid       : {check['conflicts'] == 0 and check['uncolored'] == 0}")
print(f"time        : {result.total_seconds * 1e3:.1f} ms (cold, "
      f"cache {session.stats.as_dict()})")

warm = session.run(spec, g)          # warm: every artifact cache-hits
print(f"warm rerun  : {warm.total_seconds * 1e3:.1f} ms "
      f"(cache {session.stats.as_dict()})")
assert (warm.colors == result.colors).all()
