"""Training driver (LM family).

Production behaviours demonstrated end-to-end on CPU:
  * deterministic restartable data pipeline (batch = f(seed, step)),
  * async checkpointing with atomic renames + keep-N GC,
  * resume from the latest complete checkpoint (elastic: pass a different
    mesh/sharding at restore and the checkpoint reshards),
  * optional int8-compressed gradient all-reduce (explicit-DP shard_map).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch gemma-7b --smoke \
      --steps 200 --batch 8 --seq-len 128 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.ckpt import (AsyncCheckpointer, latest_step, restore_checkpoint)
from repro.configs import get_arch
from repro.data.pipelines import TokenPipeline
from repro.models import transformer as tfm
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import compress_init, compressed_psum


def build_step(cfg, opt_cfg, *, compress: bool = False, mesh=None):
    if not compress:
        @jax.jit
        def step(params, opt, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: tfm.loss_fn(p, batch, cfg), has_aux=True)(params)
            p2, o2, om = adamw_update(grads, opt, params, opt_cfg)
            return p2, o2, {**metrics, **om, "loss": loss}
        return step

    from functools import partial
    from jax.sharding import PartitionSpec as P

    assert mesh is not None

    @jax.jit
    def step(params, opt, err, batch):
        def dp_grads(params, batch, err):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: tfm.loss_fn(p, batch, cfg), has_aux=True)(params)
            grads, err2 = compressed_psum(grads, err, "data")
            return loss, grads, err2

        sharded = jax.shard_map(
            dp_grads, mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: P("data"), batch), P()),
            out_specs=(P(), P(), P()), check_vma=False)
        loss, grads, err2 = sharded(params, batch, err)
        p2, o2, om = adamw_update(grads, opt, params, opt_cfg)
        return p2, o2, err2, {"loss": loss, **om}
    return step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--compress", action="store_true",
                    help="int8 gradient all-reduce (explicit DP)")
    args = ap.parse_args()

    arch = get_arch(args.arch)
    cfg = arch.make_smoke() if args.smoke else arch.make_config()
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20,
                          total_steps=args.steps)
    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq_len,
                         global_batch=args.batch)

    params, _ = tfm.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    start = 0
    ck = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ck:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            state = restore_checkpoint(args.ckpt_dir, last,
                                       {"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            start = last + 1
            print(f"resumed from step {last}")

    mesh = None
    err = None
    if args.compress:
        mesh = jax.make_mesh((jax.device_count(),), ("data",))
        err = compress_init(params)
    step_fn = build_step(cfg, opt_cfg, compress=args.compress, mesh=mesh)

    n_par = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_par / 1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq_len}")
    t0 = time.perf_counter()
    for step in range(start, args.steps):
        batch = pipe.batch_at(step)
        if args.compress:
            params, opt, err, m = step_fn(params, opt, err, batch)
        else:
            params, opt, m = step_fn(params, opt, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(m["loss"])
            tok_s = (step - start + 1) * args.batch * args.seq_len \
                / (time.perf_counter() - t0)
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} tok/s {tok_s:,.0f}",
                  flush=True)
        if ck and step % args.ckpt_every == 0 and step > start:
            ck.save(step, {"params": params, "opt": opt})
    if ck:
        ck.save(args.steps - 1, {"params": params, "opt": opt})
        ck.wait()
    print("done.")


if __name__ == "__main__":
    main()
