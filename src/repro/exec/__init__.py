"""Unified execution sessions (DESIGN.md §9).

``ExecutionSpec`` freezes the full static configuration of a coloring
run; ``Session`` owns the ONE keyed compile cache behind the host,
outlined and distributed Pipes. ``Session.run(spec, g)`` is the one way
to color a graph; ``Session.run_batch`` and ``Session.stream`` color
many graphs under the same cache.
"""
from repro.exec.spec import ExecutionSpec
from repro.exec.session import (CacheStats, Session, default_session,
                                reset_default_session)

__all__ = ["ExecutionSpec", "CacheStats", "Session", "default_session",
           "reset_default_session"]
