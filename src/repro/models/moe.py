"""Mixture-of-Experts FFN with expert parallelism.

Design (DESIGN.md §6): activations are sharded over the data axes and
*replicated* over the model axis; experts are sharded over the model axis.
Each model-shard dispatches the (replicated) local tokens to its own expert
slice through a capacity-bucketed buffer — the same static-shape compaction
idiom the coloring engine uses for worklists — computes its experts, and
the combine is a single psum over the model axis. No all-to-all is needed;
per-layer collective cost equals a dense TP FFN (one psum of (T, d)).

FSDP composition: expert weights are additionally sharded over the fsdp
(data/pod) axes on the expert-ff dimension and all-gathered per layer
inside the shard_map (the scan-over-layers overlaps this gather with the
previous layer's compute).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoESettings:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01


def _capacity(tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    cap = math.ceil(tokens * top_k * cf / n_experts)
    return max(8, -(-cap // 8) * 8)


def router_topk(x2d: jax.Array, w_router: jax.Array, top_k: int
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (gates (T,k) fp32, expert ids (T,k) int32, aux loss scalar)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * sum_e f_e * p_e
    e = w_router.shape[-1]
    f = jnp.zeros((e,), jnp.float32).at[eids.reshape(-1)].add(
        1.0 / (eids.shape[0] * top_k))
    p_mean = probs.mean(axis=0)
    aux = e * jnp.sum(f * p_mean)
    return gates, eids.astype(jnp.int32), aux


def expert_compute(xt: jax.Array, gates: jax.Array, eids: jax.Array,
                   w_in: jax.Array, w_gate: jax.Array, w_out: jax.Array, *,
                   e_offset, e_local: int, capacity: int) -> jax.Array:
    """Capacity-bucketed dispatch -> batched expert matmul -> combine.

    xt (T, d); w_in/w_gate (El, d, f); w_out (El, f, d). Static shapes
    throughout; overflow tokens beyond ``capacity`` per expert are dropped
    (standard capacity-factor semantics). Experts are gated (SwiGLU).
    """
    t, d = xt.shape
    k = eids.shape[1]
    flat_e = eids.reshape(-1) - e_offset                       # (T*k,)
    ok = (flat_e >= 0) & (flat_e < e_local)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    onehot = jnp.where(ok[:, None],
                       flat_e[:, None] == jnp.arange(e_local)[None, :], False)
    pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1     # (T*k, El)
    pos_of = jnp.sum(jnp.where(onehot, pos, 0), axis=1)        # (T*k,)
    keep = ok & (pos_of < capacity)
    slot = jnp.where(keep, flat_e * capacity + pos_of, e_local * capacity)

    buf = jnp.zeros((e_local * capacity + 1, d), xt.dtype)
    buf = buf.at[slot].set(xt[flat_tok], mode="drop")
    buf = buf[:-1].reshape(e_local, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    h = jax.nn.silu(h) * g
    y = jnp.einsum("ecf,efd->ecd", h, w_out).reshape(e_local * capacity, d)

    gathered = y[jnp.where(keep, slot, 0)] * keep[:, None].astype(y.dtype)
    scale = gates.reshape(-1)[:, None].astype(y.dtype)
    out = jnp.zeros((t, d), y.dtype).at[flat_tok].add(gathered * scale)
    return out


def moe_ffn(x: jax.Array, p: dict, cfg: MoESettings, *, mesh=None,
            model_axis: str = "model", batch_axes: tuple = (),
            fsdp_axes: tuple = ()) -> tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (out (B, S, d), aux loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k

    def local(x_l, wr, w_in, w_gate, w_out, *, e_local, dist):
        t_ = x_l.shape[0] * x_l.shape[1]
        xt = x_l.reshape(t_, d)
        gates, eids, aux = router_topk(xt, wr, k)
        capacity = _capacity(t_, k, e, cfg.capacity_factor)
        e_off = jax.lax.axis_index(model_axis) * e_local if dist else 0
        out = expert_compute(xt, gates, eids, w_in, w_gate, w_out,
                             e_offset=e_off, e_local=e_local,
                             capacity=capacity)
        if dist:
            out = jax.lax.psum(out, model_axis)
            aux = jax.lax.pmean(aux, batch_axes + (model_axis,))
        return out.reshape(x_l.shape), aux

    if mesh is None:
        return local(x, p["router"], p["we_in"], p["we_gate"], p["we_out"],
                     e_local=e, dist=False)

    n_model = mesh.shape[model_axis]
    e_local = e // n_model
    assert e_local * n_model == e, (e, n_model)
    x_spec = P(batch_axes or None, None, None)
    fa = fsdp_axes or None

    def sharded(x_l, wr, w_in, w_gate, w_out):
        if fsdp_axes:
            w_in = jax.lax.all_gather(w_in, fsdp_axes, axis=2, tiled=True)
            w_gate = jax.lax.all_gather(w_gate, fsdp_axes, axis=2, tiled=True)
            w_out = jax.lax.all_gather(w_out, fsdp_axes, axis=1, tiled=True)
        return local(x_l, wr, w_in, w_gate, w_out, e_local=e_local, dist=True)

    fn = jax.shard_map(sharded, mesh=mesh,
                       in_specs=(x_spec, P(),
                                 P(model_axis, None, fa),
                                 P(model_axis, None, fa),
                                 P(model_axis, fa, None)),
                       out_specs=(x_spec, P()),
                       check_vma=False)
    return fn(x, p["router"], p["we_in"], p["we_gate"], p["we_out"])
