"""The one general traffic generator: a mix's data file in, what a run
drives out.

A traffic mix is a JSON file under ``bench/traffic/`` (parameters only,
no code) whose ``kind`` names the runner that drives it,
``bench/runners/<kind>.py``. One kind exists so far:

``solo``
    one graph of the configuration's size, built from the seed, colored
    back to back by one session. Nothing to schedule.

Every part of a run that draws from the seed takes its own sub-seed
(``sub_seed``), so adding a part does not change the others' draws.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
TRAFFIC_DIR = BENCH / "traffic"
RUNNERS_DIR = BENCH / "runners"


def load_traffic(name: str) -> dict:
    mix = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    kind = mix.get("kind")
    if not isinstance(kind, str) or not (RUNNERS_DIR / f"{kind}.py").is_file():
        raise ValueError(f"traffic {name!r}: no runner "
                         f"bench/runners/{kind}.py for its kind")
    return mix


def sub_seed(seed: int, *path: int) -> int:
    """A 32-bit seed for one part of a run, derived from the run's seed
    (any non-negative integer) and the part's position."""
    return int(np.random.SeedSequence([int(seed), *path]).generate_state(1)[0])
