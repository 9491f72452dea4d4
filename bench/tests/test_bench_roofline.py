"""The least bytes of a dense sweep, against a count by hand."""
import importlib.util
from pathlib import Path

import numpy as np

METRIC = Path(__file__).resolve().parents[1] / "metrics" / \
    "kernel.dense_roofline.py"


def roofline():
    spec = importlib.util.spec_from_file_location("roofline", METRIC)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_least_bytes_by_hand():
    # a triangle 0-1-2 and a pendant 2-3, given with a duplicate edge,
    # a reversed copy and a self loop: 4 undirected edges, 8 entries
    src = np.array([0, 1, 2, 2, 1, 3, 3])
    dst = np.array([1, 2, 0, 3, 0, 3, 2])
    m = roofline()
    entries = m.directed_entries(src, dst, 4)
    assert entries == 8
    # 8 int32 neighbour ids read, 4 nodes x (color + base) read and written
    assert m.least_bytes(4, entries) == 8 * 4 + 4 * 2 * 4 * 2 == 96


def test_share_from_a_trace():
    from types import SimpleNamespace as NS

    src = np.array([0, 1, 2, 2])
    dst = np.array([1, 2, 0, 3])
    red = NS(program_time=lambda pats: (2.0, 0.002)
             if "dense_step_impl" in pats else None)
    run = NS(reduction=red, traffic={"kind": "solo"},
             results=[NS(mode_trace="DDS")], graph_of=[0],
             edges=[(src, dst, 4)],
             device={"kind": "TPU v5 lite"})
    # two dense steps in 2 ms: 1 ms a step for 96 bytes at 819 GB/s
    assert abs(roofline().read(run) - 100 * 96 / 819e9 / 1e-3) < 1e-15


def test_share_counts_each_graphs_bytes():
    from types import SimpleNamespace as NS

    # the hand-counted graph (96 bytes) and a path 0-1 on 2 nodes
    # (2 entries, 2 nodes: 8 + 32 = 40 bytes); 3 dense steps in 3 ms
    tri = (np.array([0, 1, 2, 2]), np.array([1, 2, 0, 3]), 4)
    path = (np.array([0]), np.array([1]), 2)
    red = NS(program_time=lambda pats: (3.0, 0.003)
             if "dense_step_impl" in pats else None)
    run = NS(reduction=red, traffic={"kind": "solo"},
             results=[NS(mode_trace="DDS"), NS(mode_trace="DS")],
             graph_of=[0, 1], edges=[tri, path],
             device={"kind": "TPU v5 lite"})
    per_step = (2 * 96 + 40) / 3
    assert abs(roofline().read(run) - 100 * per_step / 819e9 / 1e-3) < 1e-12


def test_unknown_device_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        roofline().hbm_bytes_per_s("TPU v9 imaginary")
