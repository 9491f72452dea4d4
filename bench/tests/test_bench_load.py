"""What a run draws from its seed: the same seed gives the same graph,
another seed another graph of the same size, and every part of a run
its own sub-seed."""
import numpy as np
import pytest

from bench import gen, harness, load
from bench.tests._tiny import tiny_run

CONFIGS = ["kron_g500", "europe_osm"]


def tiny_config(name):
    return tiny_run(next(c["name"] for c in harness.load_benchmark()
                         ["workloads"] if c["config"] == name)).config


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40 + 1])
@pytest.mark.parametrize("config", CONFIGS)
def test_same_seed_same_graph(config, seed):
    cfg = tiny_config(config)
    a = gen.edges(cfg, load.sub_seed(seed, 1))
    b = gen.edges(cfg, load.sub_seed(seed, 1))
    assert a[2] == b[2]
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


@pytest.mark.parametrize("config", CONFIGS)
def test_seeds_give_other_graphs_of_one_size(config):
    cfg = tiny_config(config)
    a = gen.edges(cfg, load.sub_seed(1, 1))
    b = gen.edges(cfg, load.sub_seed(2, 1))
    assert a[2] == b[2] and a[0].size == b[0].size
    assert not (np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1]))


def test_sub_seeds_differ_and_fit_32_bits():
    s = {load.sub_seed(2 ** 35, 2, i) for i in range(100)}
    assert len(s) == 100 and max(s) < 2 ** 32


def test_committed_mixes_load():
    assert load.load_traffic("solo")["kind"] == "solo"


def test_rounds_color_every_graph_alike():
    from bench.cells import run_cell

    run = run_cell(tiny_run("kron_g500.solo"))
    graphs = run.traffic["graphs"]
    assert graphs == len(run.edges) > 1
    # whole rounds only: each graph colored as often as the others
    assert len(run.results) % graphs == 0
    assert run.graph_of == list(range(graphs)) * (len(run.results) // graphs)
    # four graphs from one seed are four different graphs
    assert len({e[0].tobytes() for e in run.edges}) == graphs
    assert run.correct
