"""Production training launcher: Alg. 1 over the LM-family archs with pjit
distribution, checkpoint/restart, and straggler-aware supervision.

On the CPU host this runs REDUCED configs end-to-end (same code path as
production, 1-device mesh); on real hardware the same entrypoint runs the
full configs on the (data, model) production mesh — only ``--mesh`` differs.

Phases per Alg. 1 (Sec. III-B): warmup (QAT@8b) -> search (theta on 20% /
W on 80% per epoch, tau annealed) -> fine-tune (argmax frozen).  The search
is the paper's workload; checkpointing captures the full state pytree
(params, NAS logits, both optimizer states, tau, step) plus the data
pipeline position so restart is bit-exact.

Usage (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --reduced \
      --steps 30 --seq 128 --batch 8 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.policy import PrecisionPolicy
from repro.config import ARCH_IDS, get_config
from repro.core import mixedprec as mp
from repro.data import pipeline as pipe
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh, make_production_mesh
from repro.models import transformer as tfm
from repro.train import checkpoint as ckpt_mod
from repro.train import steps as steps_mod


def build_batch_iter(cfg, seq: int, global_batch: int, seed: int = 0):
    extra = {}
    if cfg.family == "audio":
        extra["frames"] = (cfg.encoder_seq, cfg.d_model)
    if cfg.family == "vlm" and cfg.n_prefix_tokens:
        extra["prefix_embeds"] = (cfg.n_prefix_tokens, cfg.d_model)
    return pipe.SyntheticLM(cfg.vocab_size, seq, global_batch, seed=seed,
                            extra=extra)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    p.add_argument("--reduced", action="store_true",
                   help="CPU-sized variant of the same family")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--warmup-steps", type=int, default=5)
    p.add_argument("--theta-every", type=int, default=5,
                   help="1 theta step per N W steps (the 20/80 split)")
    p.add_argument("--anneal-every", type=int, default=10,
                   help="steps per 'epoch' for tau annealing")
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--lam", type=float, default=1e-10)
    p.add_argument("--objective", default="size", choices=["size", "energy"])
    p.add_argument("--train-compute", default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="matmul arithmetic of the training phases (int8 = "
                        "dynamic int8 GEMMs with stochastically rounded "
                        "backward, repro.qtrain)")
    p.add_argument("--sr-seed", type=int, default=0,
                   help="base seed of the int8 stochastic rounding")
    p.add_argument("--lut", default="tpu_bw", choices=["tpu_bw", "mpic"])
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=20)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--production-mesh", action="store_true",
                   help="use the 16x16 mesh (requires 256 devices)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    hp = steps_mod.TrainHParams.for_arch(
        cfg, lr=args.lr, lam=args.lam, objective=args.objective,
        lut_name=args.lut, warmup_steps=min(args.warmup_steps, 100),
        total_steps=args.steps, train_compute=args.train_compute,
        sr_seed=args.sr_seed)
    print("resolved policy:",
          steps_mod._train_policy(
              hp, PrecisionPolicy.search(cfg.quant.tau0),
              jnp.zeros((), jnp.int32)),
          f"(search phase; opt_state_dtype={hp.opt_state_dtype})")

    mesh = (make_production_mesh() if args.production_mesh
            else make_test_mesh())
    rules = shd.ShardingRules(mesh)

    state = steps_mod.init_train_state(cfg, hp, jax.random.PRNGKey(args.seed))
    state_sh = rules.tree_shardings(state)
    state = jax.device_put(state, state_sh)

    data = build_batch_iter(cfg, args.seq, args.batch, seed=args.seed)

    mgr = None
    if args.ckpt_dir:
        mgr = ckpt_mod.CheckpointManager(args.ckpt_dir)
        if args.resume:
            restored, step0, meta = mgr.restore_latest(state, state_sh)
            if restored is not None:
                state = restored
                data.state.step = int(meta.get("data_step", 0))
                print(f"resumed from step {step0}")

    warm = jax.jit(steps_mod.make_qat_warmup_step(cfg, hp),
                   in_shardings=(state_sh, shd.batch_specs(
                       mesh, next(iter([data._gen(0)])))),
                   donate_argnums=(0,))
    train = jax.jit(steps_mod.make_train_step(cfg, hp), donate_argnums=(0,))
    theta = jax.jit(steps_mod.make_theta_step(cfg, hp,
                                              args.seq * args.batch),
                    donate_argnums=(0,))

    t_start = time.time()
    it = iter(data)
    step = int(state["step"])
    while step < args.steps:
        batch = next(it)
        t0 = time.time()
        if step < hp.warmup_steps:
            state, metrics = warm(state, batch)
            phase = "warmup"
        elif step % args.theta_every == 0:
            state, metrics = theta(state, batch)
            phase = "theta"
        else:
            state, metrics = train(state, batch)
            phase = "W"
        step = int(state["step"])
        if step % args.anneal_every == 0:
            state = steps_mod.anneal_epoch(state, cfg)
        if step % 5 == 0 or step == args.steps:
            extras = {k: float(v) for k, v in metrics.items()}
            print(f"step {step:5d} [{phase:6s}] "
                  + " ".join(f"{k}={v:.4f}" for k, v in extras.items())
                  + f" tau={float(state['tau']):.3f}"
                  + f" dt={time.time() - t0:.2f}s", flush=True)
        if mgr and step % args.ckpt_every == 0:
            mgr.save(step, state, meta={"data_step": data.state.step,
                                        "arch": cfg.name})
    if mgr:
        mgr.save(args.steps, state, meta={"data_step": data.state.step,
                                          "arch": cfg.name}, block=True)
    print(f"done: {args.steps} steps in {time.time() - t_start:.1f}s")


if __name__ == "__main__":
    main()
