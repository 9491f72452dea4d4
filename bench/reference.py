"""The plain reference: what a coloring must satisfy, checked on the
benchmark's own edge list.

It imports nothing of the program and reads none of its graph arrays:
the edges are the ones ``bench/gen`` made from the seed, before the
program normalized them. The guarantees of every configuration:

* complete: each of the ``n`` nodes holds a color ``>= 0``;
* proper: no edge ``(u, v)`` with ``u != v`` joins two nodes of one color;
* the color count the program reports is its palette, ``max + 1``.

Colors are int32 and no step of a coloring is floating point, so there
is no lower precision to fall back to: the control of ``correct`` breaks
a guarantee instead (``bench/control.py``).
"""
from __future__ import annotations

import numpy as np


def check(src: np.ndarray, dst: np.ndarray, n: int, colors,
          reported_colors: "int | None") -> dict:
    """Counts of each guarantee broken by one coloring (all 0 when sound)."""
    c = np.asarray(colors)
    if c.shape != (n,):
        # a result of the wrong length colors nothing it can be held to
        return {"uncolored_nodes": n, "conflict_edges": 0,
                "color_count_gap": 0}
    uncolored = int(np.count_nonzero(c < 0))
    cs, cd = c[src], c[dst]
    conflicts = int(np.count_nonzero((src != dst) & (cs >= 0) & (cs == cd)))
    palette = int(c.max()) + 1 if n else 0
    gap = 0 if reported_colors is None else abs(int(reported_colors)
                                                - palette)
    return {"uncolored_nodes": uncolored, "conflict_edges": conflicts,
            "color_count_gap": gap}


def color_count(colors) -> int:
    """Distinct colors a coloring uses (the ``colors`` metric)."""
    c = np.asarray(colors)
    return int(np.unique(c[c >= 0]).size)
