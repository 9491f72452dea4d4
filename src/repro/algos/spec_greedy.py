"""``spec-greedy`` — speculative first-fit coloring with iterated conflict
repair (Rokos et al., "A Fast and Scalable Graph Coloring Algorithm for
Multi-core and Many-core Architectures").

Structure: every worklist vertex speculatively takes the smallest color
not used by its neighbours' *snapshot* colors (first-fit mex); conflicts
are detected and repaired in the NEXT sweep, fused with that sweep's
re-assignment, so each iteration is detect+repair in a single pass over
one gathered neighbour tile — exactly the existing fused one-gather
kernel (``kernels/fused_step.py`` / ``ipgc.fused_*_step``), which this
engine reuses rather than reimplementing (the point of the subsystem:
same machinery, different algorithm contract).

Contrast with ``ipgc``: IPGC's reference semantics are two-phase —
assign, then resolve *within the same iteration* (a second gather).
Spec-greedy's contract is Rokos' deferred detect-and-repair: there is no
same-iteration resolve, ever — ``resolve_fused`` pins the fused family
regardless of the engine's per-backend default, making the algorithm's
identity independent of how the caller tuned the IPGC fast path.

Tie-break: random hash priority (Rokos' deterministic vertex-id repair
order degenerates to O(N) sweeps on chain graphs — same reason
``baselines.vb_color`` hashes; see its docstring). Because repaired
vertices re-run first-fit against an advancing window base, the final
palette can carry gaps; ``finalize`` compacts it and reports the true
distinct count (quality sits between IPGC and JPL).

Shard-safe: the distributed fused steps are bit-identical to the local
fused steps (DESIGN.md §6), so the declaration holds by construction.
"""
from __future__ import annotations

import dataclasses

from repro.algos.base import Algorithm, _compact_palette, init_ipgc_state
from repro.core import ipgc


@dataclasses.dataclass(frozen=True)
class SpecGreedy(Algorithm):
    name: str = "spec-greedy"
    shard_safe: bool = True
    #: reuses the ipgc fused steps, so it inherits their batch contract
    batch_safe: bool = True
    default_priority: str = "hash"

    def init_state(self, ig):
        return init_ipgc_state(ig)

    def step_impls(self, fused: bool):
        return ipgc.fused_dense_step_impl, ipgc.fused_sparse_step_impl

    def step_fns(self, fused: bool):
        return ipgc.step_fns(True)

    def resolve_fused(self, fused, *, default):
        return True                       # deferred repair IS the algorithm

    def make_dist_steps(self, ig_local, mesh, node_axes, *, window: int,
                        fused: bool, exchange: str = "dense", boundary=None,
                        thresh: int | None = None):
        from repro.core.distributed import (make_dist_dense_step,
                                            make_dist_sparse_step,
                                            shard_graph)
        sg = shard_graph(ig_local, mesh, node_axes)
        dense = make_dist_dense_step(ig_local, mesh, node_axes,
                                     window=window, fused=True,
                                     exchange=exchange, boundary=boundary,
                                     thresh=thresh, sg=sg)
        sparse = make_dist_sparse_step(ig_local, mesh, node_axes,
                                       window=window, fused=True,
                                       exchange=exchange, boundary=boundary,
                                       thresh=thresh, sg=sg)
        return dense, sparse

    def finalize(self, colors):
        return _compact_palette(colors)
