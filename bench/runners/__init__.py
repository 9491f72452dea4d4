"""Runners, one file per traffic ``kind``, found by the name a mix's
file gives: ``bench/runners/<kind>.py`` with ``drive(run, counter, *,
control=False)``, which builds the inputs from the seed, warms up and
measures the window, and ``judge(run)``, which holds the window's
answers to the reference and sets ``run.checks``."""
