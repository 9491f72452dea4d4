"""step.dense_fill: the share of the entries the dense steps gather that
belong to the rows they run.

Layer: the steps (``core/ipgc.py``). Read from the program's own counter
on each coloring of the window: ``ColoringResult.dense_entries``, the
adjacency entries of the worklist rows each dense step ran (live), and
``dense_slots``, the entries it gathered to do so, padding included (on
the ELL layouts every row's ELL slots and the hub tail it reads; on
csr-segment the padded edge array). The share is the sum of live
entries over the sum of slots. None when no dense step ran, or where the
program keeps no such counter. Moves ``color_s``: it says how much of a
dense sweep is padding and finished rows, the cost the switch to sparse
steps weighs.
"""
UNIT = "%"


def read(run):
    if run.traffic["kind"] != "solo":
        return None
    live = sum(sum(getattr(r, "dense_entries", None) or ())
               for r in run.results)
    slots = sum(sum(getattr(r, "dense_slots", None) or ())
                for r in run.results)
    if slots == 0:
        return None
    return 100.0 * live / slots
