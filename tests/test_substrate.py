"""Substrate tests: checkpointing, elasticity, data determinism, gradient
compression, sharding rules, hlocost loop correction."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.ckpt import (AsyncCheckpointer, latest_step, restore_checkpoint,
                        save_checkpoint)
from repro.data.pipelines import RecsysPipeline, TokenPipeline
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import (compress_init, dequantize_int8,
                                     quantize_int8)

# The elasticity/sharding substrate modules are not part of this repo (the
# seed ships the coloring substrate only); their tests skip with a recorded
# reason instead of hiding the whole module behind an unconditional guard.
try:
    from repro.dist import sharding as shd
    _HAVE_DIST_SHARDING = True
except ImportError:                                   # pragma: no cover
    _HAVE_DIST_SHARDING = False
try:
    from repro.ft.elastic import StragglerMonitor, plan_mesh, survivors_mesh
    _HAVE_FT_ELASTIC = True
except ImportError:                                   # pragma: no cover
    _HAVE_FT_ELASTIC = False

requires_dist_sharding = pytest.mark.skipif(
    not _HAVE_DIST_SHARDING,
    reason="repro.dist.sharding is not present in this repo (coloring "
           "substrate seed); sharding-rule coverage test not runnable")
requires_ft_elastic = pytest.mark.skipif(
    not _HAVE_FT_ELASTIC,
    reason="repro.ft.elastic is not present in this repo (coloring "
           "substrate seed); elasticity tests not runnable")


def _tree():
    k = jax.random.PRNGKey(0)
    return {"a": jax.random.normal(k, (8, 16)),
            "nested": {"b": jnp.arange(10, dtype=jnp.int32),
                       "c": jnp.ones((3,), jnp.bfloat16)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 7, t)
    assert latest_step(str(tmp_path)) == 7
    back = restore_checkpoint(str(tmp_path), 7, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_atomicity(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    # a stale .tmp dir (simulated crash) must not be visible as a step
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert latest_step(str(tmp_path)) == 1


def test_async_checkpointer_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        ck.save(s, t)
    ck.wait()
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2 and steps[-1] == "step_00000004"


def test_restore_with_resharding(tmp_path):
    """Elastic restart: restore onto a (trivially different) sharding."""
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t)
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()), t)
    back = restore_checkpoint(str(tmp_path), 3, t, shardings=sh)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_data_pipeline_deterministic():
    p = TokenPipeline(vocab=100, seq_len=16, global_batch=8, seed=3)
    a, b = p.batch_at(5), p.batch_at(5)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    c = p.batch_at(6)
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(c["tokens"]))
    # host slicing partitions the global batch
    h0 = p.host_slice(5, 0, 2)
    h1 = p.host_slice(5, 1, 2)
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]),
        np.asarray(a["tokens"]))
    r = RecsysPipeline(n_dense=4, n_sparse=3, vocab=50, global_batch=8)
    assert r.batch_at(0)["sparse"].shape == (8, 3, 1)


@requires_ft_elastic
def test_elastic_mesh_planning():
    assert plan_mesh(512, model_parallel=16, pods=2) == (2, 16, 16)
    assert plan_mesh(256, model_parallel=16) == (16, 16)
    # losing 8 hosts x 4 chips = 32 chips drops 2 data rows
    assert survivors_mesh((16, 16), list(range(8)), 4) == (14, 16)
    assert survivors_mesh((2, 16, 16), list(range(8)), 4) == (2, 15, 16)


@requires_ft_elastic
def test_straggler_rebalance():
    mon = StragglerMonitor(n_hosts=4)
    for h, t in [(0, 1.0), (1, 1.0), (2, 1.0), (3, 2.0)]:
        for _ in range(5):
            mon.observe(h, t)
    assert mon.stragglers() == [3]
    sizes = mon.rebalance_batch(256, granule=8)
    assert sum(sizes) == 256
    assert sizes[3] < sizes[0]


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6), st.integers(1, 64))
def test_int8_quantization_bounded_error(rows, cols):
    rng = np.random.default_rng(rows * 100 + cols)
    x = jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s, x.shape)
    scale = np.abs(np.asarray(x)).max(axis=1, keepdims=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err <= scale / 127.0 * 0.5 + 1e-7).all()


def test_compressed_psum_single_device():
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P
    g = {"w": jnp.asarray([[1.0, -2.0, 3.0]])}
    err = compress_init(g)

    def f(g, e):
        from repro.optim.compression import compressed_psum
        return compressed_psum(g, e.error, "data")

    red, new_e = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()), check_vma=False)(g, err)
    np.testing.assert_allclose(np.asarray(red["w"]), [[1.0, -2.0, 3.0]],
                               atol=0.02)


@requires_dist_sharding
def test_sharding_rules_cover_all_logical_axes():
    rules = shd.make_rules(multi_pod=True)
    from repro.configs import ARCH_IDS, get_arch
    from repro.models import transformer as tfm
    for arch_id in ["gemma-7b", "qwen3-moe-30b-a3b"]:
        cfg = get_arch(arch_id).make_config()
        _, axes = tfm.init_params(cfg, jax.random.PRNGKey(0), abstract=True)
        for leaf in jax.tree.leaves(
                axes, is_leaf=lambda x: isinstance(x, tuple)):
            for ax in leaf:
                assert ax in rules, ax


def test_adamw_descends_quadratic():
    p = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(p)
    cfg = AdamWConfig(lr=0.3, weight_decay=0.0, warmup_steps=0,
                      total_steps=100, min_lr_ratio=1.0)
    for _ in range(60):
        g = jax.tree.map(lambda w: 2 * w, p)
        p, opt, _ = adamw_update(g, opt, p, cfg)
    assert float(jnp.abs(p["w"]).max()) < 0.5


def test_hlocost_loop_correction():
    from repro.launch import hlocost

    def f(x, w):
        def body(c, wi):
            return jax.nn.relu(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 64), jnp.float32),
        jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)).compile().as_text()
    res = hlocost.analyze(txt)
    assert res["flops"] == 5 * 2 * 32 * 64 * 64
    assert res["hbm_bytes"] > 5 * 32 * 64 * 4   # at least the loop traffic
