"""Beyond-paper optimizations keep exact/near-exact semantics:
int8 KV decode, owner-computes GraphSAGE, flash nested-remat grads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (KVCache, decode_attention,
                                    decode_attention_q8, flash_attention,
                                    quantize_kv)
from repro.models.gnn import common as gcommon
from repro.models.gnn import graphsage as sage
from repro.models.transformer import (LMConfig, decode_step, forward,
                                      init_params, prefill)

KEY = jax.random.PRNGKey(0)


def test_int8_decode_matches_bf16_within_tolerance():
    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                   head_dim=16, d_ff=128, vocab=128, dtype=jnp.float32)
    params, _ = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, 128)
    full, _, _ = forward(params, toks, cfg)
    qc = KVCache.init(cfg.n_layers, 2, 20, cfg.n_kv_heads, cfg.head_dim,
                      dtype=jnp.int8)
    logits = None
    for t in range(16):
        logits, qc = decode_step(params, toks[:, t:t + 1], qc, cfg)
    ref = np.asarray(full[:, 15])
    rel = np.max(np.abs(np.asarray(logits) - ref)) / np.max(np.abs(ref))
    assert rel < 0.03, rel


def test_decode_attention_q8_vs_fp():
    b, s, hk, g, d = 2, 32, 2, 2, 16
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hk, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hk, d))
    q = jax.random.normal(jax.random.PRNGKey(3), (b, 1, hk * g, d))
    lens = jnp.asarray([20, 32], jnp.int32)
    want = decode_attention(q, k, v, lens)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    got = decode_attention_q8(q, kq, ks, vq, vs, lens)
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel < 0.05, rel


def test_owner_computes_matches_reference_single_shard():
    """On a 1-device mesh every edge is local, so owner-computes must be
    exactly the reference forward."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = sage.SAGEConfig(d_in=8, d_hidden=16, n_classes=5)
    params, _ = sage.init_params(cfg, KEY)
    batch = gcommon.random_graph_batch(KEY, 24, 96, 8, n_classes=5)
    want = sage.forward_full(params, batch, cfg)
    got = sage.forward_full_owner(params, batch, cfg, mesh=mesh,
                                  node_axes=("data",))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_remat_same_values_and_grads():
    q = jax.random.normal(KEY, (1, 128, 2, 16))

    def loss(q, rc):
        return (flash_attention(q, q, q, q_chunk=32, k_chunk=32,
                                remat_chunks=rc) ** 2).sum()

    v0, g0 = jax.value_and_grad(lambda q: loss(q, False))(q)
    v1, g1 = jax.value_and_grad(lambda q: loss(q, True))(q)
    np.testing.assert_allclose(float(v0), float(v1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=1e-5, atol=1e-6)


def test_flash_remat_reduces_residual_memory():
    q = jax.ShapeDtypeStruct((2, 1024, 4, 32), jnp.float32)

    def make(rc):
        def loss(q):
            return (flash_attention(q, q, q, q_chunk=128, k_chunk=128,
                                    remat_chunks=rc) ** 2).sum()
        return jax.jit(jax.grad(loss)).lower(q).compile() \
            .memory_analysis().temp_size_in_bytes

    assert make(True) < make(False) / 3


def test_adaptive_window_preserves_validity():
    from repro.core import verify_coloring
    from repro.exec import ExecutionSpec, default_session
    from repro.graphs import make_graph
    for name in ("europe_osm_s", "kron_g500-logn21_s"):
        g = make_graph(name, scale=0.02)
        r = default_session().run(ExecutionSpec(window="auto"), g)
        verify_coloring(g, r.colors, context=name)
