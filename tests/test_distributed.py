"""Distributed (shard_map) coloring steps + sharded Pipe vs the reference
engine: bit-identity of both step kinds, full-driver equivalence on
simulated multi-device meshes, and the communication-volume invariant."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ipgc, verify_coloring
from repro.core.distributed import (EXCHANGE_COUNTS, make_dist_dense_step,
                                    make_dist_sparse_step)
from repro.core.worklist import full_worklist
from repro.exec import ExecutionSpec, Session, default_session
from repro.graphs import build_graph, make_graph, validate_coloring
from repro.graphs.partition import prepare_partition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_forced_devices(code: str, n_devices: int = 8) -> str:
    """Run ``code`` in a subprocess with forced host-platform devices."""
    env = {**os.environ, "PYTHONPATH": "src",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.parametrize("name", ["europe_osm_s", "kron_g500-logn21_s"])
def test_dist_dense_step_matches_reference(name):
    g = make_graph(name, scale=0.02)
    ig = ipgc.prepare(g)
    n = ig.n_nodes
    mesh = jax.make_mesh((1,), ("data",))
    step = make_dist_dense_step(ig, mesh, ("data",), window=128)

    colors_d = ipgc.init_colors(n)
    colors_r = ipgc.init_colors(n)
    base_d = jnp.zeros((n,), jnp.int32)
    base_r = jnp.zeros((n,), jnp.int32)
    wl_d = full_worklist(n)
    wl_r = full_worklist(n)
    for _ in range(4):
        colors_d, base_d, wl_d = step(colors_d, base_d, wl_d)
        colors_r, base_r, wl_r = ipgc.dense_step(ig, colors_r, base_r, wl_r,
                                                 window=128, impl="jnp")
        np.testing.assert_array_equal(np.asarray(colors_d),
                                      np.asarray(colors_r))
        np.testing.assert_array_equal(np.asarray(wl_d.mask),
                                      np.asarray(wl_r.mask))
        assert int(wl_d.count) == int(wl_r.count)


def test_dist_step_multishard_subprocess():
    """Both step kinds, both variants, on a real 8-device (host-platform)
    mesh and a hub-heavy graph: the owner-block steps must be bit-identical
    to the single-device reference steps (colors, base, mask, count)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import ipgc
from repro.core.distributed import make_dist_dense_step, make_dist_sparse_step
from repro.core.worklist import full_worklist
from repro.graphs import build_graph
rng = np.random.default_rng(0)
n = 512
src = rng.integers(0, n, 3000); dst = rng.integers(0, n, 3000)
g = build_graph(src, dst, n, name="t", ell_cap=8)   # force a COO tail
ig = ipgc.prepare(g)
assert ig.n_hub > 0
mesh = jax.make_mesh((8,), ("data",))
for fused in (False, True):
    dstep = make_dist_dense_step(ig, mesh, ("data",), window=64, fused=fused)
    sstep = make_dist_sparse_step(ig, mesh, ("data",), window=64, fused=fused)
    dref, sref = ipgc.step_fns(fused)
    cd, cr = ipgc.init_colors(n), ipgc.init_colors(n)
    bd = br = jnp.zeros((n,), jnp.int32)
    wd, wr = full_worklist(n), full_worklist(n)
    for _ in range(2):
        cd, bd, wd = dstep(cd, bd, wd)
        cr, br, wr = dref(ig, cr, br, wr, window=64, impl="jnp")
        np.testing.assert_array_equal(np.asarray(cd), np.asarray(cr))
        assert int(wd.count) == int(wr.count)
    for _ in range(6):
        cd, bd, wd = sstep(cd, bd, wd)
        cr, br, wr = sref(ig, cr, br, wr, window=64, impl="jnp")
        np.testing.assert_array_equal(np.asarray(cd), np.asarray(cr))
        np.testing.assert_array_equal(np.asarray(wd.mask), np.asarray(wr.mask))
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(br))
        assert int(wd.count) == int(wr.count)
print("MULTISHARD_OK")
"""
    assert "MULTISHARD_OK" in _run_forced_devices(code)


def test_color_distributed_multishard_subprocess():
    """Acceptance: the full sharded Pipe on 1/2/8-shard meshes reproduces
    the host-loop engine exactly — colors (mapped back to the original
    labeling), iteration count and mode trace — on >= 3 suite graphs."""
    code = """
import jax, numpy as np
from repro.core import verify_coloring
from repro.exec import ExecutionSpec, default_session
from repro.graphs import make_graph
from repro.graphs.partition import prepare_partition
for name in ["europe_osm_s", "kron_g500-logn21_s", "hollywood-2009_s"]:
    g = make_graph(name, scale=0.01)
    for s in (1, 2, 8):
        r_d = default_session().run(ExecutionSpec(regime="dist", n_shards=s),
                                    g)
        g2, relabel = prepare_partition(g, s)
        r_h = default_session().run(ExecutionSpec(fused=True), g2)
        verify_coloring(g, r_d.colors, context=f"{name}/shards_{s}")
        np.testing.assert_array_equal(r_d.colors,
                                      r_h.colors[relabel[:g.n_nodes]])
        assert r_d.iterations == r_h.iterations, (name, s)
        assert r_d.mode_trace == r_h.mode_trace, (name, s)
        assert "S" in r_d.mode_trace or "D" in r_d.mode_trace
print("DIST_ENGINE_OK")
"""
    assert "DIST_ENGINE_OK" in _run_forced_devices(code)


def test_dist_two_phase_ell_kinds_multishard_subprocess():
    """The two-phase dist steps read each shard's rows of the static
    tie-break bits (``IPGCGraph.ell_wins``): on 4 simulated devices,
    every ELL kind, under both exchange paths, reproduces the host
    two-phase engine (colors, iterations, mode trace)."""
    code = """
import numpy as np
from repro.core import verify_coloring
from repro.exec import ExecutionSpec, default_session
from repro.graphs import get_dataset
from repro.graphs.partition import prepare_partition
for kind in ("pure-ell", "ell-tail", "hub-split"):
    g = get_dataset("kron_g500-logn21_s", scale=0.01, layout=kind,
                    **({} if kind == "pure-ell" else {"ell_cap": 16}))
    g2, relabel = prepare_partition(g, 4)
    r_h = default_session().run(ExecutionSpec(fused=False), g2)
    for ex in ("dense", "auto"):
        r = default_session().run(ExecutionSpec(
            regime="dist", n_shards=4, fused=False, exchange=ex), g)
        verify_coloring(g, r.colors, context=f"{kind}/{ex}")
        np.testing.assert_array_equal(r.colors, r_h.colors[relabel[:g.n_nodes]])
        assert (r.iterations, r.mode_trace) == (r_h.iterations,
                                                r_h.mode_trace), (kind, ex)
print("ELL_KINDS_OK")
"""
    assert "ELL_KINDS_OK" in _run_forced_devices(code, n_devices=4)


def test_dist_engine_full_run_valid():
    g = make_graph("hollywood-2009_s", scale=0.02)
    ig = ipgc.prepare(g)
    n = ig.n_nodes
    mesh = jax.make_mesh((1,), ("data",))
    step = make_dist_dense_step(ig, mesh, ("data",), window=128)
    colors = ipgc.init_colors(n)
    base = jnp.zeros((n,), jnp.int32)
    wl = full_worklist(n)
    for _ in range(200):
        colors, base, wl = step(colors, base, wl)
        if int(wl.count) == 0:
            break
    verify_coloring(g, np.asarray(colors[:n]))


# ---------------------------------------------------------------------------
# distributed sparse step + sharded Pipe (in-process, 1-shard mesh)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_dist_sparse_step_matches_reference(fused):
    """On one shard the per-shard compaction degenerates to the global one,
    so dist sparse must be bit-identical to the reference sparse step —
    including the compacted items order."""
    rng = np.random.default_rng(3)
    n = 512
    g = build_graph(rng.integers(0, n, 2500), rng.integers(0, n, 2500), n,
                    name="t", ell_cap=8)          # hub side-channel active
    ig = ipgc.prepare(g)
    assert ig.n_hub > 0
    mesh = jax.make_mesh((1,), ("data",))
    dstep = make_dist_dense_step(ig, mesh, ("data",), window=32, fused=fused)
    sstep = make_dist_sparse_step(ig, mesh, ("data",), window=32, fused=fused)
    dref, sref = ipgc.step_fns(fused)
    cd, cr = ipgc.init_colors(n), ipgc.init_colors(n)
    bd = br = jnp.zeros((n,), jnp.int32)
    wd, wr = full_worklist(n), full_worklist(n)
    cd, bd, wd = dstep(cd, bd, wd)
    cr, br, wr = dref(ig, cr, br, wr, window=32, impl="jnp")
    for _ in range(8):
        cd, bd, wd = sstep(cd, bd, wd)
        cr, br, wr = sref(ig, cr, br, wr, window=32, impl="jnp")
        np.testing.assert_array_equal(np.asarray(cd), np.asarray(cr))
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(br))
        np.testing.assert_array_equal(np.asarray(wd.mask), np.asarray(wr.mask))
        np.testing.assert_array_equal(np.asarray(wd.items),
                                      np.asarray(wr.items))
        assert int(wd.count) == int(wr.count)


@pytest.mark.parametrize("name", ["europe_osm_s", "kron_g500-logn21_s"])
def test_color_distributed_matches_host_engine(name):
    """Driver equivalence on the in-process mesh: same colors, iteration
    count and mode trace as the host-loop Pipe on the repartitioned graph,
    with colors returned in the ORIGINAL labeling."""
    g = make_graph(name, scale=0.01)
    r_d = default_session().run(ExecutionSpec(regime="dist", n_shards=1), g)
    g2, relabel = prepare_partition(g, 1)
    r_h = default_session().run(ExecutionSpec(fused=True), g2)
    verify_coloring(g, r_d.colors)
    np.testing.assert_array_equal(r_d.colors, r_h.colors[relabel[:g.n_nodes]])
    assert r_d.iterations == r_h.iterations
    assert r_d.mode_trace == r_h.mode_trace
    assert len(r_d.counts) == r_d.iterations


def test_color_dist_mode_dispatch():
    """``regime="dist"`` routes through the sharded Pipe, forwards
    ``fused``, and a shared cache store reproduces the uncached run
    without rebuilding the jitted steps."""
    g = make_graph("kron_g500-logn21_s", scale=0.01)
    dist = ExecutionSpec(regime="dist", n_shards=1)
    r = default_session().run(dist, g)
    verify_coloring(g, r.colors)
    np.testing.assert_array_equal(r.colors, Session().run(dist, g).colors)
    two_phase = ExecutionSpec(regime="dist", n_shards=1, fused=False)
    r2p = default_session().run(two_phase, g)
    np.testing.assert_array_equal(r2p.colors,
                                  Session().run(two_phase, g).colors)
    cache: dict = {}
    a = Session(cache=cache).run(dist, g)
    assert len(cache) == 1
    b = Session(cache=cache).run(dist, g)
    assert len(cache) == 1                     # reused, not rebuilt
    np.testing.assert_array_equal(a.colors, b.colors)
    np.testing.assert_array_equal(a.colors, r.colors)
    assert a.mode_trace == b.mode_trace == r.mode_trace


def test_color_distributed_degenerate_policies():
    """The sharded Pipe supports the paper's degenerate baselines too —
    the persistent worklist keeps both modes correct on their own."""
    g = make_graph("europe_osm_s", scale=0.01)
    traces = {}
    for mode in ("topology", "data"):
        r = default_session().run(
            ExecutionSpec(regime="dist", n_shards=1, mode=mode), g)
        verify_coloring(g, r.colors, context=mode)
        traces[mode] = set(r.mode_trace)
    assert traces == {"topology": {"D"}, "data": {"S"}}


def test_color_distributed_edge_cases():
    # 1-node graph (the only edge is a removed self loop) — padding to the
    # 8-aligned block makes the real node a minority of its own shard
    dist = ExecutionSpec(regime="dist", n_shards=1)
    one = build_graph(np.array([0]), np.array([0]), 1, name="one")
    r = default_session().run(dist, one)
    assert validate_coloring(one, r.colors) == {
        "conflicts": 0, "uncolored": 0, "n_colors": 1}
    # empty-after-preprocessing graph
    empty = build_graph(np.array([3]), np.array([3]), 8, name="empty")
    r = default_session().run(dist, empty)
    v = validate_coloring(empty, r.colors)
    assert v["conflicts"] == 0 and v["uncolored"] == 0 and v["n_colors"] == 1


# ---------------------------------------------------------------------------
# communication-volume invariant (trace-time)
# ---------------------------------------------------------------------------

def test_exchange_count_invariant():
    """Exactly ONE psum-based color exchange per distributed iteration for
    both step kinds in the driver-default fused form (4N bytes/device/iter,
    DESIGN.md §6); the two-phase forms perform exactly two (speculate +
    undo)."""
    g = make_graph("kron_g500-logn21_s", scale=0.01)
    g2, _ = prepare_partition(g, 1)
    ig = ipgc.prepare(g2)
    n = ig.n_nodes
    mesh = jax.make_mesh((1,), ("data",))
    colors = ipgc.init_colors(n)
    base = jnp.zeros((n,), jnp.int32)
    wl = full_worklist(n)
    for fused, want in [(True, 1), (False, 2)]:
        for make in (make_dist_dense_step, make_dist_sparse_step):
            step = make(ig, mesh, ("data",), window=32, fused=fused)
            # reset-scoped measurement (obs/metrics.py): zeroed inside,
            # outer accounting restored on exit — no cross-test leakage
            with EXCHANGE_COUNTS.scope() as ec:
                jax.eval_shape(step, colors, base, wl)
                assert ec["color_psum"] == want, (make.__name__, fused)


def test_split_tail_by_owner():
    """Each shard holds exactly the tail entries of its own hub rows, in
    the segment layout the hub passes read (ascending slots, tail_start
    ranges), with a packing bound no shard's rows exceed."""
    from repro.core.distributed import split_tail
    g = make_graph("kron_g500-logn21_s", scale=0.02)
    s_count = 4
    g2, _ = prepare_partition(g, s_count)
    ig = ipgc.prepare(g2)
    nh, seg_bound, hub_slot, tails = split_tail(ig, s_count)
    n, blk = ig.n_nodes, ig.n_nodes // s_count
    assert ig.n_hub > 0 and 0 < nh < ig.n_hub
    valid = np.asarray(ig.tail_valid)
    src = np.asarray(ig.tail_src)[valid]
    dst = np.asarray(ig.tail_dst)[valid]
    t_src, t_dst, t_valid, t_slot, t_start, hub_ids = (
        a.reshape(s_count, -1) for a in tails)
    for s in range(s_count):
        mine = (src // blk) == s
        v = t_valid[s]
        np.testing.assert_array_equal(t_src[s][v], src[mine])
        np.testing.assert_array_equal(t_dst[s][v], dst[mine])
        assert (t_dst[s][~v] == n).all() and (t_slot[s][~v] == nh).all()
        np.testing.assert_array_equal(t_slot[s][v], hub_slot[src[mine]])
        assert (np.diff(t_slot[s]) >= 0).all()
        for h in range(nh):
            rows = t_src[s][t_start[s][h]:t_start[s][h + 1]]
            assert (rows == hub_ids[s][h]).all()
        owned = np.bincount(src[mine] - s * blk, minlength=blk)
        top = np.cumsum(np.sort(owned)[::-1])
        for k, bound in enumerate(seg_bound):
            assert top[min(2 ** k, blk) - 1] <= bound


def test_dist_packed_hub_tails_multishard_subprocess():
    """Sparse dist steps pack the tail entries of their own hub rows: on 4
    simulated devices, a graph whose floor bucket packs, both step
    families and every exchange mode equal the host engine."""
    code = """
import numpy as np
import jax.numpy as jnp
from repro.core import ipgc, verify_coloring
from repro.exec import ExecutionSpec, default_session
import jax
from repro.core.distributed import _local_graph_view, shard_graph
from repro.graphs import generators, ingest
from repro.graphs.layout import run_pipeline
from repro.graphs.partition import prepare_partition
src, dst, n = generators.edges_rgg(32768, 16, 0)
g = run_pipeline(ingest.from_arrays(src, dst, n, name="rgg"),
                 layout="ell-tail", ell_cap=8)
g2, relabel = prepare_partition(g, 4)
ig = ipgc.prepare(g2)
sg = shard_graph(ig, jax.make_mesh((4,), ("data",)), ("data",))
# shard 0's operands, as its local step sees them
ell, wins, hub_slot, prio, tails = jax.tree.map(np.asarray, sg.arrays)
view = _local_graph_view(ig, sg, ig.n_nodes, jax.tree.map(jnp.asarray, (
    ell[:ig.n_nodes // 4], wins[:ig.n_nodes // 4], hub_slot, prio,
    tuple(a[:a.shape[0] // 4] for a in tails))))
assert ipgc._hub_packed(view, jnp.zeros((1024,), jnp.int32)) is not None
for fused in (True, False):
    r_h = default_session().run(ExecutionSpec(fused=fused), g2)
    ref = r_h.colors[relabel[:g.n_nodes]]
    for ex in ("dense", "boundary", "auto"):
        r = default_session().run(ExecutionSpec(
            regime="dist", n_shards=4, exchange=ex, fused=fused), g)
        verify_coloring(g, r.colors, context=f"{fused}/{ex}")
        np.testing.assert_array_equal(r.colors, ref)
        assert (r.iterations, r.mode_trace) == (r_h.iterations,
                                                r_h.mode_trace), (fused, ex)
print("PACKED_TAILS_OK")
"""
    assert "PACKED_TAILS_OK" in _run_forced_devices(code, n_devices=4)
