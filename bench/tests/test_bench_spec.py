"""BENCHMARK.json against the rules its files follow: every name found,
every metric with a reader that declares its unit."""
import json
import re
from pathlib import Path

import pytest

from bench import harness, load

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"]
    assert set(cfg["reduced"]) == set(data["reduced"])
    assert all(k in data for k in cfg["reduced"])
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_published_sizes_kept_unless_reduced(cfg):
    # a size the source publishes and the run sets is the published one,
    # unless ``reduced`` lists it
    data = json.loads((ROOT / cfg["file"]).read_text())
    for key, value in data["published"].items():
        if key in data and key not in cfg["reduced"]:
            assert data[key] == value, key
    assert data["source"] == cfg["source"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cells_resolve(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200
    harness.load_config(cell["config"])
    kind = load.load_traffic(cell["traffic"])["kind"]
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, cell["name"],
                                                   "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(BENCH, cell["name"], "per_layer")
    assert (load.RUNNERS_DIR / f"{kind}.py").is_file()


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if "bound" in m:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        e2e = {x["name"]: x for x in BENCH["end_to_end"]}
        moved = e2e[m["moves"]]
        # every cell that reads this metric reports what it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        reader = harness.load_reader(m["name"])
        assert reader.UNIT == m["unit"]
