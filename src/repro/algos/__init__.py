"""Pluggable coloring-algorithm subsystem (DESIGN.md §7).

The ``Algorithm`` protocol + registry decouple *what* is colored from
*how* it is dispatched: every registered algorithm runs under the same
hybrid Pipe machinery (host loop, chunked outlining, capacity ladder,
``Policy`` switching, and — where the algorithm declares itself
shard-safe — the sharded ``shard_map`` Pipe).

Built-ins registered at import:

  ipgc         the paper's engine (bit-identical to the pre-subsystem
               engine); speculative windowed mex + same-iteration
               resolve; shard-safe.
  jpl          Jones–Plassmann–Luby random-priority independent sets; no
               resolve phase; fast rounds, many colors; host+outlined only.
  spec-greedy  Rokos-style speculative first-fit with deferred fused
               detect-and-repair; shard-safe.
"""
from repro.algos.base import (Algorithm, algorithm_names,  # noqa: F401
                              get_algorithm, register)
from repro.algos.ipgc_algo import IPGC
from repro.algos.jpl import JPL
from repro.algos.spec_greedy import SpecGreedy

register(IPGC())
register(JPL())
register(SpecGreedy())
