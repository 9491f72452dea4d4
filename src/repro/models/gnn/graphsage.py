"""GraphSAGE (Hamilton et al. 2017) — mean aggregator.

Two execution paths:
  * full-graph: edge-index gather + segment-mean over the whole graph
    (full_graph_sm / ogb_products shapes);
  * sampled minibatch: layered fan-out blocks from ``graphs.sampler``
    (minibatch_lg shape, Reddit-scale) — the path that shares the paper's
    worklist/frontier machinery.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import common as mcommon
from repro.models.gnn import common as g
from repro.graphs.sampler import SampledBlocks


@dataclasses.dataclass(frozen=True)
class SAGEConfig:
    name: str = "graphsage-reddit"
    n_layers: int = 2
    d_in: int = 602
    d_hidden: int = 128
    n_classes: int = 41
    aggregator: str = "mean"
    fanouts: tuple = (25, 10)
    dtype: object = jnp.float32


def init_params(cfg: SAGEConfig, key: jax.Array, *, abstract: bool = False):
    f = mcommon.ParamFactory(key, cfg.dtype, abstract=abstract)
    p = {}
    d = cfg.d_in
    for i in range(cfg.n_layers):
        out = cfg.d_hidden if i < cfg.n_layers - 1 else cfg.n_classes
        p[f"self{i}"] = f.dense((d, out), ("gnn_in", "gnn_out"))
        p[f"nbr{i}"] = f.dense((d, out), ("gnn_in", "gnn_out"))
        p[f"b{i}"] = f.zeros((out,), ("gnn_out",))
        d = out
    return mcommon.split_tree(p)


def _layer(p, i, h_self, h_nbr_agg, last: bool):
    y = h_self @ p[f"self{i}"] + h_nbr_agg @ p[f"nbr{i}"] + p[f"b{i}"]
    if not last:
        y = jax.nn.relu(y)
        y = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), 1e-6)
    return y


def forward_full(params, batch: g.GraphBatch, cfg: SAGEConfig) -> jax.Array:
    """Full-graph forward: (N, d_in) -> (N, n_classes)."""
    n = batch.node_feat.shape[0]
    h = batch.node_feat
    for i in range(cfg.n_layers):
        h_ext = jnp.concatenate([h, jnp.zeros_like(h[:1])], axis=0)
        msg = h_ext[jnp.minimum(batch.edge_src, n)]
        agg = g.scatter_mean(msg, batch.edge_dst, n)
        h = _layer(params, i, h, agg, last=(i == cfg.n_layers - 1))
    return h


def forward_sampled(params, feats: jax.Array, blocks: SampledBlocks,
                    cfg: SAGEConfig) -> jax.Array:
    """Minibatch forward over layered fan-out blocks.

    feats: global (N, d_in) feature table (gathered per hop).
    Returns (B, n_classes) seed logits.
    """
    b = blocks.seeds.shape[0]
    # gather raw features at each level: level 0 = seeds, level k = hop k
    levels = [feats[blocks.seeds]]
    for hop in blocks.hops:
        levels.append(feats[hop.reshape(-1)])
    # aggregate top-down: at layer i, level j is updated from level j+1
    for i in range(cfg.n_layers):
        last = i == cfg.n_layers - 1
        new_levels = []
        for j in range(cfg.n_layers - i):
            fan = cfg.fanouts[j]
            parent = levels[j]                              # (P, d)
            child = levels[j + 1].reshape(parent.shape[0], fan, -1)
            mask = blocks.masks[j].reshape(parent.shape[0], fan, 1)
            agg = (child * mask).sum(1) / jnp.maximum(mask.sum(1), 1.0)
            new_levels.append(_layer(params, i, parent, agg, last=last))
        levels = new_levels
    return levels[0]


def forward_full_owner(params, batch: g.GraphBatch, cfg: SAGEConfig, *,
                       mesh, node_axes: tuple) -> jax.Array:
    """Owner-computes full-graph forward (beyond-paper optimisation,
    EXPERIMENTS.md §Perf B1).

    The GSPMD path scatters edge-sharded messages into node-sharded sums —
    O(E*d) cross-shard traffic. Here edges are *pre-partitioned by dst
    owner* (the engine's node block partitioner): inside a shard_map each
    shard all-gathers the (N, d) feature table once per layer and runs a
    purely local gather + segment-mean for its node block. Collective
    volume per layer drops from O(E*d) to O(N*d) — ~avg_degree x less.
    Requires: edge_dst sharded s.t. every edge lives on dst's owner shard
    (graphs.partition.repartition + sort by dst block).
    """
    from jax.sharding import PartitionSpec as P

    n = batch.node_feat.shape[0]
    n_shards = 1
    for a in node_axes:
        n_shards *= mesh.shape[a]
    blk = n // n_shards

    def local(h, src, dst):
        # h: local (blk, d) node block; edges: local slice, dst in-block
        out = h
        for i in range(cfg.n_layers):
            h_full = jax.lax.all_gather(out, node_axes, axis=0, tiled=True)
            h_ext = jnp.concatenate([h_full, jnp.zeros_like(h_full[:1])], 0)
            msg = h_ext[jnp.minimum(src, n)]
            dst_local = jnp.where(dst < n, dst % blk, blk)
            agg = jax.ops.segment_sum(msg, dst_local, num_segments=blk + 1)
            cnt = jax.ops.segment_sum(jnp.ones_like(msg[:, :1]), dst_local,
                                      num_segments=blk + 1)
            agg = (agg / jnp.maximum(cnt, 1.0))[:blk]
            out = _layer(params, i, out, agg, last=(i == cfg.n_layers - 1))
        return out

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(node_axes, None), P(node_axes),
                                 P(node_axes)),
                       out_specs=P(node_axes, None), check_vma=False)
    return fn(batch.node_feat, batch.edge_src, batch.edge_dst)


def loss_full(params, batch: g.GraphBatch, cfg: SAGEConfig):
    logits = forward_full(params, batch, cfg)
    loss = mcommon.cross_entropy(logits, batch.node_label)
    return loss, {"ce": loss}


def loss_sampled(params, feats, blocks, labels, cfg: SAGEConfig):
    logits = forward_sampled(params, feats, blocks, cfg)
    loss = mcommon.cross_entropy(logits, labels)
    return loss, {"ce": loss}
