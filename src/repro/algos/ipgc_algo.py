"""``ipgc`` — the paper's engine, refactored behind the Algorithm protocol.

Pure delegation to ``core/ipgc.py``: the step impls, jitted step pair,
state initialisation and finalize are exactly the functions the engine
called before the subsystem existed, so ``engine.color(g, algo="ipgc")``
is bit-identical (colors, iteration count, mode trace) to the
pre-refactor engine in host-loop, outlined and dist-hybrid modes.
"""
from __future__ import annotations

import dataclasses

from repro.algos.base import Algorithm, init_ipgc_state
from repro.core import ipgc


@dataclasses.dataclass(frozen=True)
class IPGC(Algorithm):
    name: str = "ipgc"
    shard_safe: bool = True
    #: the core/ipgc.py steps are the reference batch-axis-safe impls
    #: (shape-static jnp ops; pad_prepared documents the inertness proof)
    batch_safe: bool = True
    default_priority: str = "hash"

    def init_state(self, ig):
        return init_ipgc_state(ig)

    def step_impls(self, fused: bool):
        return ((ipgc.fused_dense_step_impl, ipgc.fused_sparse_step_impl)
                if fused else (ipgc.dense_step_impl, ipgc.sparse_step_impl))

    def step_fns(self, fused: bool):
        return ipgc.step_fns(fused)

    def make_dist_steps(self, ig_local, mesh, node_axes, *, window: int,
                        fused: bool, exchange: str = "dense", boundary=None,
                        thresh: int | None = None):
        # local import: distributed.py imports the engine (result type)
        from repro.core.distributed import (make_dist_dense_step,
                                            make_dist_sparse_step,
                                            shard_graph)
        sg = shard_graph(ig_local, mesh, node_axes)
        dense = make_dist_dense_step(ig_local, mesh, node_axes,
                                     window=window, fused=fused,
                                     exchange=exchange, boundary=boundary,
                                     thresh=thresh, sg=sg)
        sparse = make_dist_sparse_step(ig_local, mesh, node_axes,
                                       window=window, fused=fused,
                                       exchange=exchange, boundary=boundary,
                                       thresh=thresh, sg=sg)
        return dense, sparse
