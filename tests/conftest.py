"""Fixtures shared by the test modules."""
import jax
import pytest


@pytest.fixture
def packed_chunk(monkeypatch):
    """Force the slots a trip of the csr-segment chunk loop reads. The
    step programs read ``ipgc.PACKED_CHUNK`` when they are traced, so
    JAX's caches are cleared around the test."""
    from repro.core import ipgc

    def force(chunk: int) -> None:
        monkeypatch.setattr(ipgc, "PACKED_CHUNK", chunk)
        jax.clear_caches()
    yield force
    jax.clear_caches()
