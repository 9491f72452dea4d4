"""Every Pallas kernel compiles natively for a TPU v5e at real widths.

The parity suites run the kernels in interpret mode, which accepts what
Mosaic (the TPU kernel compiler) refuses: dynamic slices of vector values,
bool ``argmax``, stores at unaligned dynamic offsets, and whole-array VMEM
blocks. These tests hand each kernel to the installed TPU compiler for a
described, unattached ``v5e:2x2`` chip on one device's sharding, at the
published size of kron_g500-logn21 (2^21 rows) with the ELL width the
layout planner gives that graph (32), and check the compiled program holds
the native kernel (``tpu_custom_call``). Nothing runs, so no chip is
needed; the topology is described inside a fixture so that importing this
file never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.compact import compact_pallas
from repro.kernels.conflict import conflict_pallas
from repro.kernels.frontier import frontier_probe_pallas
from repro.kernels.fused_compact import fused_compact_pallas
from repro.kernels.fused_step import fused_step_pallas
from repro.kernels.jpl_prio import jpl_extrema_pallas
from repro.kernels.mex_window import mex_window_pallas

R = 2 ** 21          # kron_g500-logn21 nodes
K = 32               # plan_layout's ELL width for it (its p90 degree, 28)
W = 128              # color window


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _kernel(name: str, tile_rows: int):
    """(fn, operand shapes) of one kernel call at R rows."""
    i32, b = jnp.int32, jnp.bool_
    tile = [((R, K), i32)] * 3
    row = ((R,), i32)
    flag = ((R,), b)
    hub = [((R, W), b), flag]
    if name == "mex_window":
        return (lambda nc, base, x: mex_window_pallas(
            nc, base, x, W, tile_rows=tile_rows),
                [tile[0], row, ((R, W), b)])
    if name == "conflict":
        return (lambda *a: conflict_pallas(*a, tile_rows=tile_rows),
                tile + [row] * 3)
    if name == "fused_step":
        return (lambda *a: fused_step_pallas(*a, W, tile_rows=tile_rows),
                tile + [row] * 4 + [flag, ((R, W), b)])
    if name == "jpl_extrema":
        return (lambda npr: jpl_extrema_pallas(npr, tile_rows=tile_rows),
                [tile[0]])
    if name == "frontier_probe":
        return frontier_probe_pallas, [((R, K), b), flag]
    if name == "compact":
        return compact_pallas, [flag]
    with_hub = name == "fused_compact-hub"

    def fc(*a):
        a = list(a) if with_hub else list(a) + [None, None]
        return fused_compact_pallas(*a, W, tile_rows=tile_rows)
    return fc, tile + [row] * 4 + [flag, flag] + (hub if with_hub else [])


def _compile_for_chip(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("name", ["mex_window", "conflict", "fused_step",
                                  "jpl_extrema", "frontier_probe",
                                  "compact"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel(name, 32)
    assert "tpu_custom_call" in _compile_for_chip(fn, shapes, one_chip)


@pytest.mark.parametrize("tile_rows", [8, 32, 128])
@pytest.mark.parametrize("name", ["fused_compact", "fused_compact-hub"])
def test_fused_compact_compiles_for_v5e(one_chip, name, tile_rows):
    """The dense fused iteration at every autotuner candidate height, with
    and without the hub operands (pure-ell vs ell-tail/hub-split)."""
    fn, shapes = _kernel(name, tile_rows)
    assert "tpu_custom_call" in _compile_for_chip(fn, shapes, one_chip)


@pytest.mark.parametrize("exchange", ["dense", "auto"])
def test_dist_steps_compile_for_v5e_2x2(topo, monkeypatch, exchange):
    """The dist-hybrid steps (one shard_map program each) compile for a
    described four-chip v5e host, with the color exchange's collectives
    in them. A kron graph at 2^14 nodes: this checks that the sharded
    programs lower for the chip, not their memory at 2^21 (a dense step
    at 2^19 takes the compiler about 45 s). The described chips hold no
    data, so the steps receive the graph arrays as shapes."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.algos import get_algorithm
    from repro.core import distributed
    from repro.graphs.layout import resolve_plan
    from repro.core.policy import exchange_threshold
    from repro.core.worklist import Worklist, bucket_capacities
    from repro.graphs import make_graph
    from repro.graphs.partition import boundary_info, prepare_partition

    s = 4
    g = make_graph("kron_g500-logn21_s", scale=0.25)
    g2, _ = prepare_partition(g, s)
    alg = get_algorithm("ipgc")
    ig = alg.prepare(g2, plan=resolve_plan(g, "ell-tail"))
    n, blk = ig.n_nodes, ig.n_nodes // s
    mesh = Mesh(np.array(topo.devices[:s]), ("data",))
    monkeypatch.setattr(distributed.jax, "device_put",
                        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                           sharding=sh))
    binfo = boundary_info(g2, s) if exchange != "dense" else None
    dense, sparse = alg.make_dist_steps(
        ig, mesh, ("data",), window=32, fused=True, exchange=exchange,
        boundary=binfo,
        thresh=exchange_threshold(n, s, exchange) if binfo else None)

    def shape(shp, dtype, *spec):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))
    views = binfo is not None
    colors = (shape((s, n + 1), jnp.int32, "data", None) if views
              else shape((n + 1,), jnp.int32))
    kw = {"bcap": binfo.capacities[-1]} if views else {}
    for step, cap in ((dense, blk), (sparse, bucket_capacities(blk)[-1])):
        wl = Worklist(mask=shape((n,), jnp.bool_, "data"),
                      items=shape((s * cap,), jnp.int32, "data"),
                      count=shape((), jnp.int32))
        hlo = step.lower(colors, shape((n,), jnp.int32, "data"), wl,
                         **kw).compile().as_text()
        assert "all-reduce" in hlo or "all-gather" in hlo


@pytest.mark.parametrize("step", ["dense", "sparse"])
def test_ell_two_phase_steps_gather_no_priorities_for_v5e(one_chip, step):
    """The two-phase pure-ELL steps, compiled for the chip at R rows of
    width 8 (the road graph's ELL width), gather neighbour colors and no
    priorities: the tie-break is the static ``ell_wins`` bits. The dense
    step holds exactly its two (R, 8) tile gathers (assign, resolve); the
    sparse step at a 2^19-row bucket three (C, 8) ones (its ELL rows,
    assign, resolve) and no priority tile."""
    import re

    from repro.core import ipgc
    from repro.core.worklist import Worklist

    k, cap = 8, 2 ** 19

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32 = jnp.int32
    ig = ipgc.IPGCGraph(
        n_nodes=R, ell_width=k, n_hub=0, ell_idx=sds((R, k), i32),
        degrees=sds((R,), i32), priority=sds((R + 1,), i32),
        tail_src=sds((8,), i32), tail_dst=sds((8,), i32),
        tail_valid=sds((8,), jnp.bool_), tail_slot=sds((8,), i32),
        hub_slot=sds((R,), i32), hub_ids=sds((1,), i32),
        tail_start=sds((1,), i32), layout_kind="pure-ell",
        ell_wins=sds((R, 1), jnp.uint32))
    rows = R if step == "dense" else cap
    wl = Worklist(mask=sds((R,), jnp.bool_), items=sds((rows,), i32),
                  count=sds((), i32))
    fn = ipgc.dense_step_impl if step == "dense" else ipgc.sparse_step_impl
    hlo = jax.jit(fn, static_argnames=("window", "impl")).lower(
        ig, sds((R + 1,), i32), sds((R,), i32), wl, window=32,
        impl="jnp").compile().as_text()
    gathers = re.findall(r"= \w+\[([\d,]*)\]\{[^}]*\} gather\(", hlo)
    if step == "dense":
        assert gathers == [f"{R},{k}"] * 2, gathers
    else:
        assert gathers.count(f"{cap},{k}") == 3, gathers


@pytest.mark.parametrize("fused", [False, True])
def test_csr_packed_sparse_step_reads_chunks_for_v5e(one_chip, fused):
    """The csr-segment packed sparse step, compiled for the chip at the
    kron cell's size (2^17 rows, 3.73M edge slots, the floor bucket of
    1,024 rows whose bound packs 1.44M slots), gathers edges and
    neighbour colors one chunk of ``PACKED_CHUNK`` slots at a time, and
    nothing of the packed cap's size: edges once (the two-phase resolve
    reads the edges its assign loop kept), neighbour colors once per
    loop (one loop fused, two in the two-phase step)."""
    import re

    from repro.core import ipgc
    from repro.core.worklist import Worklist

    n, m, rows = 2 ** 17, 3_728_792, 1024
    bound = (16384, 24576, 40960, 73728, 122880, 180224, 294912, 458752,
             655360, 983040, 1441792, 1835008, 2359296, 2883584)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    ig = ipgc.IPGCGraph(
        n_nodes=n, ell_width=1, n_hub=0, ell_idx=sds((n, 1)),
        degrees=sds((n,)), priority=sds((n + 1,)), tail_src=sds((8,)),
        tail_dst=sds((8,)), tail_valid=sds((8,), jnp.bool_),
        tail_slot=sds((8,)), hub_slot=sds((n,)), hub_ids=sds((1,)),
        tail_start=sds((1,)), layout_kind="csr-segment",
        edge_src=sds((m,)), edge_dst=sds((m,)), seg_bound=bound)
    cap = ipgc.packed_cap(ig, rows, m)
    assert cap == 1441792 and ipgc.packed_chunk(cap) == ipgc.PACKED_CHUNK
    wl = Worklist(mask=sds((n,), jnp.bool_), items=sds((rows,)),
                  count=sds(()))
    fn = ipgc.fused_sparse_step_impl if fused else ipgc.sparse_step_impl
    hlo = jax.jit(fn, static_argnames=("window", "impl")).lower(
        ig, sds((n + 1,)), sds((n,)), wl, window=128,
        impl="jnp").compile().as_text()
    sizes = [int(s) for s in
             re.findall(r"= \w+\[(\d+)\]\{[^}]*\} gather\(", hlo)]
    assert sizes.count(ipgc.PACKED_CHUNK) == (2 if fused else 3), sizes
    assert max(sizes) == ipgc.PACKED_CHUNK, sizes
