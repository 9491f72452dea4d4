"""The DIMACS10 random geometric graph generator (``bench/gen/rgg.py``)
against a brute-force reference of its recipe, the committed
configuration, the ``step.dense_fill`` reader on hand-built runs, and
the ``rgg_n_2_24.solo`` cell at a small size through the real runner."""
import copy
import json
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import gen, harness, load
from bench.cells import run_cell
from bench.gen import rgg
from bench.tests._tiny import SEED, tiny_run

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "rgg_n_2_24.json"
SEEDS = [0, 1, 2 ** 33 + 5]


def brute_force(n: int, factor: float, seed: int) -> set:
    """The recipe read literally: every pair of the points closer than
    the radius, compared all against all, as (i, j) draw indices, i < j."""
    p = rgg.points(n, seed)
    r = rgg.radius(n, factor)
    d2 = ((p[:, None, 0] - p[None, :, 0]) ** 2
          + (p[:, None, 1] - p[None, :, 1]) ** 2)
    i, j = np.nonzero(np.triu(d2 < r * r, 1))
    return set(zip(i.tolist(), j.tolist()))


def draw_order(n: int, factor: float, seed: int) -> np.ndarray:
    """The draw index of each node id (ids follow the bins, row-major)."""
    p = rgg.points(n, seed)
    g = int(1.0 / rgg.radius(n, factor))
    bx = np.minimum((p[:, 0] * g).astype(np.int64), g - 1)
    by = np.minimum((p[:, 1] * g).astype(np.int64), g - 1)
    return np.argsort(by * g + bx, kind="stable")


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("n", [2 ** 10, 2 ** 12])
def test_edges_are_the_brute_force_pairs(n, seed):
    src, dst, got_n = rgg.edges(n, 0.55, seed)
    assert got_n == n
    order = draw_order(n, 0.55, seed)
    a, b = order[src], order[dst]
    got = set(zip(np.minimum(a, b).tolist(), np.maximum(a, b).tolist()))
    assert len(got) == src.size            # each undirected edge once
    assert got == brute_force(n, 0.55, seed)


def test_ids_follow_the_bins():
    # a node's neighbours lie within a few bin rows of it
    n = 2 ** 12
    src, dst, _ = rgg.edges(n, 0.55, 3)
    bins_a_side = int(1.0 / rgg.radius(n, 0.55))
    per_row = n / bins_a_side
    assert np.abs(src - dst).max() < 4 * per_row


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_simple_graph(seed):
    a = rgg.edges(2 ** 11, 0.55, seed)
    b = rgg.edges(2 ** 11, 0.55, seed)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    src, dst, n = a
    assert n == 2 ** 11
    assert not np.any(src == dst)
    key = np.minimum(src, dst) * n + np.maximum(src, dst)
    assert np.unique(key).size == key.size
    other = rgg.edges(2 ** 11, 0.55, seed + 1)
    assert not np.array_equal(other[0], src)


def test_mean_degree_is_the_recipes():
    # points near the border lose part of their disc: the expected
    # degree is (n - 1) * (pi r^2 - 8/3 r^3 + r^4 / 2) in the unit
    # square, 0.95 ln n less that loss
    n = 2 ** 14
    src, _, _ = rgg.edges(n, 0.55, 11)
    r = rgg.radius(n, 0.55)
    want = (n - 1) * (np.pi * r ** 2 - 8 / 3 * r ** 3 + r ** 4 / 2)
    assert np.pi * 0.55 ** 2 == pytest.approx(0.95, abs=1e-3)
    assert 2 * src.size / n == pytest.approx(want, rel=0.03)


def test_committed_config():
    cfg = json.loads(CONFIG.read_text())
    assert cfg["generator"] == "rgg"
    assert cfg["radius_factor"] == cfg["published"]["radius_factor"] == 0.55
    assert 2 ** cfg["scale"] <= cfg["published"]["nodes"] == 2 ** 24
    assert cfg["spec"] == {} and cfg["solo_graph"]["layout"] == "auto"
    # the committed file through the generator lookup, at a cut scale
    small = dict(cfg, scale=10)
    src, dst, n = gen.edges(small, load.sub_seed(SEED, 1, 0))
    assert n == 2 ** 10 and src.size == dst.size > 0
    assert src.dtype.kind == dst.dtype.kind == "i"


def fill():
    return harness.load_reader("step.dense_fill")


def test_dense_fill_sums_live_over_slots():
    run = NS(traffic={"kind": "solo"},
             results=[NS(dense_entries=[80, 60], dense_slots=[100, 100]),
                      NS(dense_entries=[50], dense_slots=[200])])
    assert fill().read(run) == pytest.approx(100 * 190 / 400)


@pytest.mark.parametrize("results", [
    [NS(mode_trace="SS", dense_entries=[], dense_slots=[])],
    [NS(mode_trace="DSS")],          # a program without the counter
], ids=["no-dense-step", "no-counter"])
def test_dense_fill_reads_nothing(results):
    run = NS(traffic={"kind": "solo"}, results=results)
    assert fill().read(run) is None


def tiny_rgg(scale: int = 11) -> harness.Run:
    """The cell at ``2**scale`` points, capped as ``bench/tests/_tiny.py``
    caps the other cells."""
    cell = harness.find_cell(harness.load_benchmark(), "rgg_n_2_24.solo")
    cfg = copy.deepcopy(harness.load_config(cell["config"]))
    cfg["scale"] = scale
    cfg["spec"] = dict(cfg.get("spec", {}), max_iter=60)
    return harness.Run(cell=cell, config=cfg,
                       traffic=load.load_traffic(cell["traffic"]),
                       seed=SEED, seconds=0.3, trace=False)


def test_cell_runs_and_counts_both_steps():
    run = run_cell(tiny_rgg())
    assert run.correct and run.failed == 0
    assert run.e2e["colors"] >= 2 and run.e2e["color_s"] > 0
    for r in run.results:
        assert "D" in r.mode_trace and "S" in r.mode_trace
        assert len(r.dense_entries) == len(r.dense_slots) \
            == r.mode_trace.count("D")
        assert all(0 < e <= s for e, s in zip(r.dense_entries,
                                                r.dense_slots))
    assert 0 < fill().read(run) <= 100
    assert 0 < harness.load_reader("step.sparse_fill").read(run) <= 100


@pytest.mark.parametrize("cell", ["kron_g500.solo", "europe_osm.solo"])
def test_dense_counter_reaches_the_runner(cell):
    run = run_cell(tiny_run(cell))
    assert run.correct
    for r in run.results:
        assert len(r.dense_entries) == len(r.dense_slots) \
            == r.mode_trace.count("D") > 0
    assert 0 < fill().read(run) <= 100
