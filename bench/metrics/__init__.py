"""Per-layer metric readers, one file per metric, found by the name
``BENCHMARK.json`` gives it: ``read(run) -> float | None``."""
