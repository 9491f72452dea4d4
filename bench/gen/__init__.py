"""Graph generators of the benchmark, found by the name a configuration
gives under ``generator``: ``bench/gen/<generator>.py`` with
``from_config(cfg, seed) -> (src, dst, n)``."""
from __future__ import annotations

import importlib


def edges(cfg: dict, seed: int):
    mod = importlib.import_module(f"bench.gen.{cfg['generator']}")
    return mod.from_config(cfg, seed)
