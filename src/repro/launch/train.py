"""Production training driver: pjit-sharded train step, checkpoint/restart,
preemption drain, straggler logging, deterministic data replay.

Usage (also callable as a library — see examples/train_end_to_end.py):

  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small \
      --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt [--mesh 1x1]
"""

from __future__ import annotations

import argparse
import functools
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.models import api
from repro import optim
from repro.data import SyntheticLM, StructuredLM
from repro import ckpt as ckpt_lib
from repro.distributed import sharding as shd
from repro.ft import PreemptionGuard, StragglerDetector
from .mesh import make_host_mesh


def make_train_step(cfg, opt_cfg, accum_steps: int = 1, policy=None):
    """Production train step. accum_steps > 1 enables gradient
    accumulation (microbatching): the global batch is processed in
    `accum_steps` sequential microbatches, dividing peak activation
    memory by the same factor — required to fit large archs' train_4k
    (see EXPERIMENTS.md §Dry-run) — at unchanged math (mean of grads).

    ``policy`` (runtime.ExecPolicy) selects the exp/kernel backends for
    the whole step; None keeps the config's legacy execution fields
    (callers that want env-var resolution pass resolve_policy(cfg), as
    the CLI main() does)."""
    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: api.loss_fn(p, cfg, batch, policy=policy))(params)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            loss, grads = grad_fn(params, batch)
        else:
            def micro(carry, mb):
                loss_acc, g_acc = carry
                loss, g = grad_fn(params, mb)
                return (loss_acc + loss,
                        jax.tree.map(jnp.add, g_acc, g)), None

            mbs = jax.tree.map(
                lambda x: x.reshape((accum_steps, -1) + x.shape[1:]), batch)
            zeros = jax.tree.map(jnp.zeros_like, params)
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.float32(0), zeros), mbs)
            loss = loss / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
        new_params, new_opt, stats = optim.update(
            grads, opt_state, params, opt_cfg)
        stats["loss"] = loss
        return new_params, new_opt, stats
    return train_step


def shard_train_step(cfg, opt_cfg, mesh, *, fsdp=False, donate=True,
                     policy=None):
    """jit the train step with explicit in/out shardings for `mesh`."""
    pspecs = shd.param_specs(cfg, mesh, fsdp=fsdp)
    ospecs = shd.opt_specs(cfg, mesh, pspecs)
    bspecs = shd.batch_specs(cfg, mesh, "train")
    stat_specs = {"grad_norm": P(), "lr": P(), "clip_scale": P(),
                  "loss": P()}
    fn = make_train_step(cfg, opt_cfg, policy=policy)
    return jax.jit(
        fn,
        in_shardings=(shd.named(mesh, pspecs), shd.named(mesh, ospecs),
                      shd.named(mesh, bspecs)),
        out_shardings=(shd.named(mesh, pspecs), shd.named(mesh, ospecs),
                       shd.named(mesh, stat_specs)),
        donate_argnums=(0, 1) if donate else ()), pspecs, ospecs, bspecs


def train(cfg, *, steps=100, batch=8, seq=256, ckpt_dir=None,
          ckpt_every=50, opt_cfg=None, mesh=None, fsdp=False,
          data="structured", log_every=10, guard=None, log=print,
          policy=None):
    """Run (or resume) a training job. Returns (params, history)."""
    opt_cfg = opt_cfg or optim.OptConfig(total_steps=steps)
    mesh = mesh or make_host_mesh()
    step_fn, pspecs, ospecs, bspecs = shard_train_step(
        cfg, opt_cfg, mesh, fsdp=fsdp, policy=policy)

    if data == "structured":
        pipe = StructuredLM(cfg.vocab, batch, seq, seed=17)
    else:
        pipe = SyntheticLM(cfg, batch, seq, seed=17)

    start_step = 0
    with mesh:
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        params = jax.device_put(params, shd.named(mesh, pspecs))
        opt_state = optim.init(params, opt_cfg)
        opt_state = jax.device_put(opt_state, shd.named(mesh, ospecs))

        if ckpt_dir and (ckpt_lib.latest_step(ckpt_dir) is not None):
            flat, manifest = ckpt_lib.restore(ckpt_dir)
            tree = ckpt_lib.unflatten_like(
                flat, {"params": params, "opt": opt_state})
            params = ckpt_lib.reshard(tree["params"],
                                      shd.named(mesh, pspecs))
            opt_state = ckpt_lib.reshard(tree["opt"],
                                         shd.named(mesh, ospecs))
            start_step = manifest["step"]
            log(f"[train] resumed from step {start_step}")

        saver = (ckpt_lib.AsyncCheckpointer(ckpt_dir)
                 if ckpt_dir else None)
        guard = guard or PreemptionGuard()
        strag = StragglerDetector()
        history = []
        bsh = shd.named(mesh, bspecs)

        for step in range(start_step, steps):
            t0 = time.perf_counter()
            hb = pipe.batch(step)
            db = jax.tree.map(
                lambda a, s: jax.device_put(jnp.asarray(a), s), hb,
                {k: bsh[k] for k in hb})
            params, opt_state, stats = step_fn(params, opt_state, db)
            if step % log_every == 0 or step == steps - 1:
                loss = float(stats["loss"])
                history.append((step, loss))
                log(f"[train] step {step:5d} loss {loss:.4f} "
                    f"gnorm {float(stats['grad_norm']):.3f} "
                    f"lr {float(stats['lr']):.2e}")
            dt = time.perf_counter() - t0
            if strag.record(step, dt):
                log(f"[train] straggler step {step}: {dt:.2f}s "
                    f"(median {strag.median:.2f}s)")
            if saver and (step + 1) % ckpt_every == 0:
                saver.save_async({"params": params, "opt": opt_state},
                                 step + 1)
            if guard.should_stop:
                log(f"[train] preemption at step {step}; draining")
                if saver:
                    saver.wait()
                    ckpt_lib.save({"params": params, "opt": opt_state},
                                  ckpt_dir, step + 1)
                return params, history
        if saver:
            saver.wait()
            ckpt_lib.save({"params": params, "opt": opt_state},
                          ckpt_dir, steps)
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--data", default="structured",
                    choices=["structured", "uniform"])
    ap.add_argument("--exp-backend", default=None,
                    choices=["exact", "vexp", "vexp_hw"],
                    help="exponential backend (default: config/env)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["pallas", "reference", "xla"],
                    help="kernel backend (default: config/env)")
    args = ap.parse_args()
    from repro.runtime import resolve_policy, use_compile_cache
    print(f"[train] compile cache: {use_compile_cache()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    policy = resolve_policy(cfg, exp_backend=args.exp_backend,
                            kernel_backend=args.kernel_backend)
    print(f"[train] policy: {policy.describe()}")
    opt_cfg = optim.OptConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(1, args.steps // 20))
    train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
          ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
          opt_cfg=opt_cfg, fsdp=args.fsdp, data=args.data, policy=policy)


if __name__ == "__main__":
    main()
