"""step.sparse_fill: the share of the entries the sparse steps gather
that belong to the rows they run.

Layer: the steps (``core/ipgc.py``). Read from the program's own counter
on each coloring of the window: ``ColoringResult.sparse_entries``, the
adjacency entries of the rows each sparse step ran (live), and
``sparse_slots``, the entries it gathered to do so, padding included.
The share is the sum of live entries over the sum of slots. None when
no sparse step ran, or where the program keeps no such counter. Moves
``color_s``: a step that gathers fewer dead entries has less to read.
"""
UNIT = "%"


def read(run):
    if run.traffic["kind"] != "solo":
        return None
    live = sum(sum(getattr(r, "sparse_entries", None) or ())
               for r in run.results)
    slots = sum(sum(getattr(r, "sparse_slots", None) or ())
                for r in run.results)
    if slots == 0:
        return None
    return 100.0 * live / slots
