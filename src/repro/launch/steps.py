"""Per-(arch x shape) step builders for training, serving and the dry-run.

``build_case(arch_id, shape_name, mesh)`` returns a ``Case`` bundling

  * ``fn``            — the jit-able step function,
  * ``args``          — abstract (ShapeDtypeStruct) inputs, weak-type
                        correct, shardable, zero allocation,
  * ``in_shardings``  — NamedSharding tree matching ``args``,
  * ``meta``          — MODEL_FLOPS and bookkeeping for the roofline.

The same builders serve the real launchers (feed real arrays instead of
the SDS tree) — the dry-run and production paths cannot drift.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ArchSpec, ShapeSpec, get_arch
from repro.dist import sharding as shd
from repro.models import common as mcommon
from repro.models import dlrm as dlrm_mod
from repro.models import transformer as tfm
from repro.models.attention import KVCache
from repro.models.gnn import common as gcommon
from repro.models.gnn import egnn as egnn_mod
from repro.models.gnn import equiformer_v2 as eqv2_mod
from repro.models.gnn import graphsage as sage_mod
from repro.models.gnn import schnet as schnet_mod
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.graphs.sampler import sample_blocks, blocks_to_graphbatch


@dataclasses.dataclass
class Case:
    arch_id: str
    shape_name: str
    fn: Callable
    args: tuple
    in_shardings: tuple
    meta: dict
    donate: tuple = ()      # argnums aliased into outputs (params/opt/cache)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _ns(mesh, *parts):
    return NamedSharding(mesh, P(*parts))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _replicated(mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def _lm_axes(rules):
    return rules["_batch"], rules["embed"] or ()


def _lm_params(cfg, mesh, rules):
    params, axes = tfm.init_params(cfg, jax.random.PRNGKey(0), abstract=True)
    shard = shd.tree_shardings(axes, mesh, rules)
    return params, shard


# fit profiles: gradient-accumulation factor + optimizer state dtype per
# arch (keeps the big-d models inside 16 GB HBM; the global batch per
# optimizer step is unchanged, bf16 m/v is the 8-bit-Adam-class tradeoff)
_MICROBATCHES = {"nemotron-4-340b": 8, "minitron-4b": 2}
_OPT_STATE_DTYPE = {"nemotron-4-340b": jnp.bfloat16}
_GRAD_ACCUM_DTYPE = {"nemotron-4-340b": jnp.bfloat16}


def lm_train_case(arch: ArchSpec, shape: ShapeSpec, mesh, rules) -> Case:
    cfg = arch.make_config()
    batch_axes, fsdp_axes = _lm_axes(rules)
    s, b = shape.params["seq_len"], shape.params["global_batch"]
    opt_cfg = AdamWConfig(
        state_dtype=_OPT_STATE_DTYPE.get(arch.arch_id, jnp.float32),
        update_in_chunks=False)
    n_micro = _MICROBATCHES.get(arch.arch_id, 1)

    def grads_of(params, batch):
        def lf(p):
            return tfm.loss_fn(p, batch, cfg, mesh=mesh,
                               batch_axes=batch_axes, fsdp_axes=fsdp_axes)
        return jax.value_and_grad(lf, has_aux=True)(params)

    def step(params, opt, batch):
        if n_micro == 1:
            (loss, metrics), grads = grads_of(params, batch)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                    + x.shape[1:]), batch)

            acc_dt = _GRAD_ACCUM_DTYPE.get(arch.arch_id, jnp.float32)

            def micro(acc, mb):
                (l, _), g = grads_of(params, mb)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(acc_dt), acc, g)
                return acc, l

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)
            gsum, losses = jax.lax.scan(micro, zeros, mbs)
            grads = jax.tree.map(lambda g_: g_ / n_micro, gsum)
            loss = losses.mean()
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}
        new_p, new_o, om = adamw_update(grads, opt, params, opt_cfg)
        return new_p, new_o, {**metrics, **om, "loss": loss}

    params, p_shard = _lm_params(cfg, mesh, rules)
    opt = jax.eval_shape(lambda p: adamw_init(p, opt_cfg.state_dtype), params)
    o_shard = type(opt)(step=_ns(mesh), m=p_shard, v=p_shard)
    batch = {"tokens": _sds((b, s), jnp.int32),
             "labels": _sds((b, s), jnp.int32)}
    b_shard = {k: _ns(mesh, batch_axes, None) for k in batch}
    tokens = b * s
    return Case(arch.arch_id, shape.name, step, (params, opt, batch),
                (p_shard, o_shard, b_shard),
                meta={"model_flops": 6 * cfg.n_active_params * tokens,
                      "tokens": tokens, "kind": "train"},
                donate=(0, 1))


def lm_prefill_case(arch: ArchSpec, shape: ShapeSpec, mesh, rules) -> Case:
    cfg = arch.make_config()
    batch_axes, fsdp_axes = _lm_axes(rules)
    s, b = shape.params["seq_len"], shape.params["global_batch"]

    def step(params, tokens):
        return tfm.prefill(params, tokens, cfg, mesh=mesh,
                           batch_axes=batch_axes, fsdp_axes=fsdp_axes)

    params, p_shard = _lm_params(cfg, mesh, rules)
    tokens = _sds((b, s), jnp.int32)
    return Case(arch.arch_id, shape.name, step, (params, tokens),
                (p_shard, _ns(mesh, batch_axes, None)),
                meta={"model_flops": 2 * cfg.n_active_params * b * s,
                      "tokens": b * s, "kind": "prefill"})


def lm_decode_case(arch: ArchSpec, shape: ShapeSpec, mesh, rules,
                   variant: str = "base") -> Case:
    cfg = arch.make_config()
    batch_axes, fsdp_axes = _lm_axes(rules)
    s, b = shape.params["seq_len"], shape.params["global_batch"]
    kv_dtype = cfg.dtype
    if variant != "base":
        # inference sharding profile: no optimizer state at serve time, so
        # drop FSDP when bf16 params fit one model shard — kills the
        # per-layer weight all-gathers (EXPERIMENTS.md §Perf B2)
        if cfg.n_params * 2 / mesh.shape["model"] < 6e9:
            fsdp_axes = ()
        if "int8" in variant:
            kv_dtype = jnp.int8            # §Perf B3: halves KV reads
        if "half" in variant:
            s = s // 2                     # KV length bucketing (paper-style)
    n_batch_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))
    if b < n_batch_shards:
        batch_axes = ()                       # B=1 long-context: no DP
    # KV cache sharding: batch over data axes when possible, sequence over
    # the model axis (long-context: over everything — see DESIGN.md)
    if batch_axes:
        cache_spec = P(None, batch_axes, "model", None, None)
    else:
        cache_spec = P(None, None, tuple(mesh.axis_names), None, None)

    def step(params, cache, tokens):
        return tfm.decode_step(params, tokens, cache, cfg, mesh=mesh,
                               batch_axes=batch_axes, fsdp_axes=fsdp_axes)

    params, p_shard = _lm_params(cfg, mesh, rules)
    if variant != "base" and not fsdp_axes:
        # replicate params over the (dropped) fsdp axes
        serve_rules = dict(rules)
        serve_rules["embed"] = None
        serve_rules["expert_ff"] = None
        _, p_shard = _lm_params(cfg, mesh, serve_rules)
    kv_shape = (cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim)
    if kv_dtype == jnp.int8:
        cache = KVCache(k=_sds(kv_shape, jnp.int8),
                        v=_sds(kv_shape, jnp.int8),
                        length=_sds((b,), jnp.int32),
                        k_scale=_sds(kv_shape[:-1], jnp.float16),
                        v_scale=_sds(kv_shape[:-1], jnp.float16))
        sc_spec = NamedSharding(mesh, P(*cache_spec[:-1]))
        c_shard = KVCache(k=NamedSharding(mesh, cache_spec),
                          v=NamedSharding(mesh, cache_spec),
                          length=_ns(mesh), k_scale=sc_spec,
                          v_scale=sc_spec)
        kv_elem_bytes = 1
    else:
        cache = KVCache(k=_sds(kv_shape, cfg.dtype),
                        v=_sds(kv_shape, cfg.dtype),
                        length=_sds((b,), jnp.int32))
        c_shard = KVCache(k=NamedSharding(mesh, cache_spec),
                          v=NamedSharding(mesh, cache_spec),
                          length=_ns(mesh))
        kv_elem_bytes = 2
    tokens = _sds((b, 1), jnp.int32)
    kv_bytes = 2 * cfg.n_layers * b * s * cfg.n_kv_heads * cfg.head_dim \
        * kv_elem_bytes
    return Case(arch.arch_id, shape.name, step, (params, cache, tokens),
                (p_shard, c_shard, _ns(mesh, batch_axes or None, None)),
                meta={"model_flops": 2 * cfg.n_active_params * b
                      + 2 * b * cfg.n_heads * cfg.head_dim * s * 2,
                      "tokens": b, "kind": "decode", "kv_bytes": kv_bytes,
                      "variant": variant},
                donate=(1,))


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

_GNN_MODS = {
    "equiformer-v2": eqv2_mod,
    "egnn": egnn_mod,
    "schnet": schnet_mod,
    "graphsage-reddit": sage_mod,
}


def _gnn_cfg(arch: ArchSpec, shape: ShapeSpec, rules):
    cfg = arch.make_config()
    if arch.arch_id == "equiformer-v2":
        chunk = min(cfg.edge_chunk, 262144)
        cfg = dataclasses.replace(cfg, edge_shard_axes=rules["_batch"],
                                  edge_chunk=chunk)
    if arch.arch_id == "graphsage-reddit" and "d_feat" in shape.params:
        cfg = dataclasses.replace(cfg, d_in=shape.params["d_feat"])
    if arch.arch_id == "egnn" and "d_feat" in shape.params:
        cfg = dataclasses.replace(cfg, d_in=shape.params["d_feat"])
    return cfg


def _gnn_flops(arch_id: str, cfg, n: int, e: int) -> int:
    """Analytic MODEL_FLOPS (fwd+bwd ~ 3x fwd for train)."""
    if arch_id == "graphsage-reddit":
        per = 2 * cfg.d_in * cfg.d_hidden + 2 * cfg.d_hidden * cfg.n_classes
        return 3 * (n * per + e * cfg.d_in * 2)
    if arch_id == "schnet":
        d, r = cfg.d_hidden, cfg.n_rbf
        per_e = 2 * r * d + 2 * d * d + d
        per_n = 4 * 2 * d * d
        return 3 * cfg.n_interactions * (e * per_e + n * per_n)
    if arch_id == "egnn":
        d = cfg.d_hidden
        per_e = 2 * (2 * d + 1) * d + 2 * d * d + 2 * d * d + 2 * d
        per_n = 2 * 2 * d * d
        return 3 * cfg.n_layers * (e * per_e + n * per_n)
    if arch_id == "equiformer-v2":
        c, L, s = cfg.channels, cfg.l_max, (cfg.l_max + 1) ** 2
        wig = sum((2 * l + 1) ** 2 for l in range(L + 1))
        rot = 2 * 2 * wig * c              # rotate in + out
        so2 = 2 * ((L + 1) * c) ** 2 + 2 * sum(
            2 * ((L + 1 - m) * c) ** 2 for m in range(1, cfg.m_max + 1))
        per_n = 2 * s * c * c * 3
        return 3 * cfg.n_layers * (e * (rot + so2) + n * per_n)
    raise ValueError(arch_id)


def _gnn_loss(arch_id: str, mod, cfg):
    def loss(params, batch, targets):
        if arch_id == "graphsage-reddit":
            logits = mod.forward_full(params, batch, cfg)
            return mcommon.cross_entropy(logits, batch.node_label)
        if arch_id == "egnn":
            pred, _ = mod.forward(params, batch, cfg)
            return jnp.mean((pred - targets) ** 2)
        pred = mod.forward(params, batch, cfg)
        return jnp.mean((pred - targets) ** 2)
    return loss


def gnn_full_case(arch: ArchSpec, shape: ShapeSpec, mesh, rules,
                  *, molecule: bool = False, variant: str = "base") -> Case:
    mod = _GNN_MODS[arch.arch_id]
    cfg = _gnn_cfg(arch, shape, rules)
    dn = rules["_batch"]
    n_shards = int(np.prod([mesh.shape[a] for a in dn]))
    gran = max(1024, n_shards)
    if molecule:
        bsz = shape.params["batch"]
        n = _round_up(shape.params["n_nodes"] * bsz, gran)
        e = _round_up(shape.params["n_edges"] * bsz, gran)
        n_graphs = bsz
    else:
        n = _round_up(shape.params["n_nodes"], gran)
        e = _round_up(shape.params["n_edges"], gran)
        if arch.arch_id == "equiformer-v2":
            e = _round_up(e, cfg.edge_chunk)
        n_graphs = 1
    d_feat = shape.params.get("d_feat", 16)
    if arch.arch_id == "graphsage-reddit":
        cfg = dataclasses.replace(cfg, d_in=d_feat)
    if arch.arch_id == "egnn":
        cfg = dataclasses.replace(cfg, d_in=d_feat)
    opt_cfg = AdamWConfig()
    loss = _gnn_loss(arch.arch_id, mod, cfg)
    owner = variant != "base" and arch.arch_id == "graphsage-reddit" \
        and not molecule

    def step(params, opt, node_feat, edge_src, edge_dst, coords, labels,
             targets):
        batch = gcommon.GraphBatch(
            node_feat=node_feat, edge_src=edge_src, edge_dst=edge_dst,
            coords=coords, node_label=labels,
            graph_id=(jnp.arange(n, dtype=jnp.int32) * n_graphs // n
                      if n_graphs > 1 else None),
            n_graphs=n_graphs)
        if owner:
            def loss_owner(p, b_, _t):
                logits = sage_mod.forward_full_owner(
                    p, b_, cfg, mesh=mesh, node_axes=rules["_batch"])
                return mcommon.cross_entropy(logits, b_.node_label)
            l, grads = jax.value_and_grad(loss_owner)(params, batch, targets)
        else:
            l, grads = jax.value_and_grad(loss)(params, batch, targets)
        new_p, new_o, om = adamw_update(grads, opt, params, opt_cfg)
        return new_p, new_o, {"loss": l, **om}

    params, axes = mod.init_params(cfg, jax.random.PRNGKey(0), abstract=True)
    p_shard = shd.tree_shardings(axes, mesh, rules)
    opt = jax.eval_shape(adamw_init, params)
    o_shard = type(opt)(step=_ns(mesh), m=p_shard, v=p_shard)
    args = (params, opt,
            _sds((n, d_feat)), _sds((e,), jnp.int32), _sds((e,), jnp.int32),
            _sds((n, 3)), _sds((n,), jnp.int32), _sds((n_graphs,)))
    shards = (p_shard, o_shard,
              _ns(mesh, dn, None), _ns(mesh, dn), _ns(mesh, dn),
              _ns(mesh, dn, None), _ns(mesh, dn), _ns(mesh))
    return Case(arch.arch_id, shape.name, step, args, shards,
                meta={"model_flops": _gnn_flops(arch.arch_id, cfg, n, e),
                      "tokens": n, "kind": "gnn_train"})


def gnn_minibatch_case(arch: ArchSpec, shape: ShapeSpec, mesh, rules) -> Case:
    mod = _GNN_MODS[arch.arch_id]
    cfg = _gnn_cfg(arch, shape, rules)
    dn = rules["_batch"]
    n = shape.params["n_nodes"]
    e = 2 * shape.params["n_edges"]        # directed entries
    bsz = shape.params["batch_nodes"]
    fanout = shape.params["fanout"]
    d_feat = shape.params["d_feat"]
    if arch.arch_id == "graphsage-reddit":
        cfg = dataclasses.replace(cfg, fanouts=fanout, d_in=d_feat)
    if arch.arch_id == "egnn":
        cfg = dataclasses.replace(cfg, d_in=d_feat)
    if arch.arch_id == "equiformer-v2":
        # sampled block has ~170k edges; single chunk
        cfg = dataclasses.replace(cfg, edge_chunk=bsz * fanout[0] *
                                  (1 + fanout[1]), edge_shard_axes=())
    opt_cfg = AdamWConfig()

    def step(params, opt, feats, coords, labels, row_ptr, col_idx, seeds,
             rng):
        blocks = sample_blocks(rng, row_ptr, col_idx, seeds, fanout)

        def loss(p):
            if arch.arch_id == "graphsage-reddit":
                logits = sage_mod.forward_sampled(p, feats, blocks, cfg)
                return mcommon.cross_entropy(logits, labels[seeds])
            batch = blocks_to_graphbatch(blocks, feats, coords, labels)
            if arch.arch_id == "egnn":
                pred, _ = mod.forward(p, batch, cfg)
            else:
                pred = mod.forward(p, batch, cfg)
            return jnp.mean(pred ** 2)

        l, grads = jax.value_and_grad(loss)(params)
        new_p, new_o, om = adamw_update(grads, opt, params, opt_cfg)
        return new_p, new_o, {"loss": l, **om}

    params, axes = mod.init_params(cfg, jax.random.PRNGKey(0), abstract=True)
    p_shard = shd.tree_shardings(axes, mesh, rules)
    opt = jax.eval_shape(adamw_init, params)
    o_shard = type(opt)(step=_ns(mesh), m=p_shard, v=p_shard)
    n_pad = _round_up(n, 1024)
    e_pad = _round_up(e, 1024)
    args = (params, opt, _sds((n_pad, d_feat)), _sds((n_pad, 3)),
            _sds((n_pad,), jnp.int32), _sds((n_pad + 1,), jnp.int32),
            _sds((e_pad,), jnp.int32), _sds((bsz,), jnp.int32),
            _sds((2,), jnp.uint32))
    shards = (p_shard, o_shard, _ns(mesh, dn, None), _ns(mesh, dn, None),
              _ns(mesh, dn), _ns(mesh), _ns(mesh, dn), _ns(mesh), _ns(mesh))
    n_sampled = bsz * (1 + fanout[0] + fanout[0] * fanout[1])
    e_sampled = bsz * fanout[0] * (1 + fanout[1])
    return Case(arch.arch_id, shape.name, step, args, shards,
                meta={"model_flops": _gnn_flops(arch.arch_id, cfg, n_sampled,
                                                e_sampled),
                      "tokens": bsz, "kind": "gnn_minibatch"})


# ---------------------------------------------------------------------------
# recsys family
# ---------------------------------------------------------------------------

def dlrm_case(arch: ArchSpec, shape: ShapeSpec, mesh, rules) -> Case:
    cfg = arch.make_config()
    dn = rules["_batch"]
    kind = shape.kind
    params, axes = dlrm_mod.init_params(cfg, jax.random.PRNGKey(0),
                                        abstract=True)
    p_shard = shd.tree_shardings(axes, mesh, rules)

    if kind == "rs_train":
        b = shape.params["batch"]
        opt_cfg = AdamWConfig()

        def step(params, opt, dense, sparse, labels):
            def lf(p):
                return dlrm_mod.loss_fn(p, {"dense": dense, "sparse": sparse,
                                            "labels": labels}, cfg)[0]
            l, grads = jax.value_and_grad(lf)(params)
            new_p, new_o, om = adamw_update(grads, opt, params, opt_cfg)
            return new_p, new_o, {"loss": l, **om}

        opt = jax.eval_shape(adamw_init, params)
        o_shard = type(opt)(step=_ns(mesh), m=p_shard, v=p_shard)
        args = (params, opt, _sds((b, cfg.n_dense)),
                _sds((b, cfg.n_sparse, cfg.hot), jnp.int32),
                _sds((b,), jnp.float32))
        shards = (p_shard, o_shard, _ns(mesh, dn, None),
                  _ns(mesh, dn, None, None), _ns(mesh, dn))
        flops = 6 * (cfg.n_params - cfg.n_sparse * cfg.vocab_per_table
                     * cfg.embed_dim) * b
    elif kind == "rs_serve":
        b = shape.params["batch"]

        def step(params, dense, sparse):
            return dlrm_mod.forward(params, dense, sparse, cfg)

        args = (params, _sds((b, cfg.n_dense)),
                _sds((b, cfg.n_sparse, cfg.hot), jnp.int32))
        shards = (p_shard, _ns(mesh, dn, None), _ns(mesh, dn, None, None))
        flops = 2 * (cfg.n_params - cfg.n_sparse * cfg.vocab_per_table
                     * cfg.embed_dim) * b
    else:                                   # rs_retrieval
        nc = shape.params["n_candidates"]
        nc_pad = _round_up(nc, 1024)

        def step(params, dense, sparse, candidates):
            return dlrm_mod.retrieval_score(params, dense, sparse,
                                            candidates, cfg)

        args = (params, _sds((1, cfg.n_dense)),
                _sds((1, cfg.n_sparse, cfg.hot), jnp.int32),
                _sds((nc_pad, cfg.embed_dim)))
        all_axes = tuple(mesh.axis_names)
        shards = (p_shard, _ns(mesh), _ns(mesh), _ns(mesh, all_axes, None))
        flops = 2 * nc_pad * cfg.embed_dim
        b = 1
    return Case(arch.arch_id, shape.name, step, args, shards,
                meta={"model_flops": flops, "tokens": b, "kind": kind})


# ---------------------------------------------------------------------------
# the paper's own engine (extra, beyond the 40 assigned cells)
# ---------------------------------------------------------------------------

def ipgc_case(arch: ArchSpec, shape: ShapeSpec, mesh, rules) -> Case:
    from repro.core import ipgc as ipgc_mod
    from repro.core.worklist import Worklist

    dn = rules["_batch"]
    n = shape.params["n_nodes"]
    k = shape.params["ell_width"]
    t_pad = max(n // 64, 1024)
    nh = max(n // 4096, 8)

    ig = ipgc_mod.IPGCGraph(
        n_nodes=n, ell_width=k, n_hub=nh,
        ell_idx=_sds((n, k), jnp.int32), degrees=_sds((n,), jnp.int32),
        priority=_sds((n + 1,), jnp.int32), tail_src=_sds((t_pad,), jnp.int32),
        tail_dst=_sds((t_pad,), jnp.int32), tail_valid=_sds((t_pad,), bool),
        tail_slot=_sds((t_pad,), jnp.int32), hub_slot=_sds((n,), jnp.int32),
        hub_ids=_sds((nh,), jnp.int32), tail_start=_sds((nh + 1,), jnp.int32))
    colors = _sds((n + 1,), jnp.int32)
    base = _sds((n,), jnp.int32)
    wl = Worklist(mask=_sds((n,), bool), items=_sds((n,), jnp.int32),
                  count=_sds((), jnp.int32))

    def step(ig, colors, base, wl):
        return ipgc_mod.dense_step(ig, colors, base, wl, window=128,
                                   impl="jnp")

    ig_shard = ipgc_mod.IPGCGraph(
        n_nodes=n, ell_width=k, n_hub=nh,
        ell_idx=_ns(mesh, dn, None), degrees=_ns(mesh, dn),
        priority=_ns(mesh), tail_src=_ns(mesh), tail_dst=_ns(mesh),
        tail_valid=_ns(mesh), tail_slot=_ns(mesh), hub_slot=_ns(mesh, dn),
        hub_ids=_ns(mesh), tail_start=_ns(mesh))
    wl_shard = Worklist(mask=_ns(mesh, dn), items=_ns(mesh, dn),
                        count=_ns(mesh))
    shards = (ig_shard, _ns(mesh), _ns(mesh, dn), wl_shard)
    # per-iteration work ~ O(N*K) compares + O(N*W) mex
    return Case(arch.arch_id, shape.name, step,
                (ig, colors, base, wl), shards,
                meta={"model_flops": n * (k + 128) * 2, "tokens": n,
                      "kind": "coloring"})


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def build_case(arch_id: str, shape_name: str, mesh: Mesh, *,
               multi_pod: bool = False, variant: str = "base") -> Case:
    arch = get_arch(arch_id)
    shape = arch.shapes[shape_name]
    rules = shd.make_rules(multi_pod=multi_pod)
    if arch.family == "lm":
        if shape.kind == "train":
            return lm_train_case(arch, shape, mesh, rules)
        if shape.kind == "prefill":
            return lm_prefill_case(arch, shape, mesh, rules)
        return lm_decode_case(arch, shape, mesh, rules, variant=variant)
    if arch.family == "gnn":
        if shape.kind == "gnn_minibatch":
            return gnn_minibatch_case(arch, shape, mesh, rules)
        return gnn_full_case(arch, shape, mesh, rules,
                             molecule=(shape.kind == "gnn_molecule"),
                             variant=variant)
    if arch.family == "recsys":
        return dlrm_case(arch, shape, mesh, rules)
    if arch.family == "paper":
        return ipgc_case(arch, shape, mesh, rules)
    raise ValueError(arch.family)
