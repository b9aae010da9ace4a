"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/run1

Single-host it runs on local devices (CPU included); multi-host it
expects ``jax.distributed.initialize`` env (TPU pods) and builds the mesh
over all devices.  Fault tolerance: resumes from the latest committed
checkpoint (params, optimizer, data position); preemption mid-step costs
at most ``--ckpt-every`` steps.

The paper's technique is first-class: ``--cbtd-gamma`` prunes every
linear with CBTD inside the jitted step (Alg. 2), and the LM data stream
is the synthetic pipeline (offline substitute).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import get_arch
from repro.core import alpha_at, cbtd_prune_tree
from repro.data.lm import LMConfig, LMDataset
from repro.distributed.sharding import param_specs
from repro.launch.elastic import best_mesh_for
from repro.launch.steps import make_train_step
from repro.models import api
from repro.training.checkpoint import CheckpointManager
from repro.training.optimizer import AdamWConfig, adamw_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--cbtd-gamma", type=float, default=None)
    ap.add_argument("--cbtd-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if jax.process_count() > 1:  # multi-host: initialize was done by env
        pass

    mesh = best_mesh_for(len(jax.devices()))
    print(f"[train] arch={cfg.name} mesh={dict(mesh.shape)} "
          f"devices={len(jax.devices())}")

    key = jax.random.key(0)
    params = api.init_params(cfg, key, jnp.float32)
    opt_state = adamw_init(params)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                          schedule="cosine", total_steps=args.steps)

    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh, cfg))
    o_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(opt_state, mesh, cfg))
    params = jax.device_put(params, p_sh)
    opt_state = jax.device_put(opt_state, o_sh)

    data = LMDataset(LMConfig(vocab=cfg.vocab, seq_len=args.seq),
                     args.batch, jax.process_index())

    step0 = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep_last=2)
        (params, opt_state), meta, ck = mgr.restore_latest((params, opt_state))
        if ck is not None:
            step0 = int(meta["step"])
            data.load_state_dict({"step": meta["data_step"]})
            params = jax.device_put(params, p_sh)
            opt_state = jax.device_put(opt_state, o_sh)
            print(f"[train] resumed from step {step0}")

    train_step = make_train_step(cfg, opt_cfg, args.seq,
                                 microbatches=args.microbatches)
    layout = api.cbtd_layout(cfg) if args.cbtd_gamma else None
    if layout:
        layout = {k: dataclasses.replace(v, gamma=args.cbtd_gamma)
                  for k, v in layout.items()}

    @jax.jit
    def prune(params, alpha):
        return cbtd_prune_tree(params, layout, alpha)

    jit_step = jax.jit(train_step, in_shardings=(p_sh, o_sh, None),
                       out_shardings=(p_sh, o_sh, None),
                       donate_argnums=(0, 1))

    with jax.set_mesh(mesh):
        t0 = time.time()
        for step in range(step0, args.steps):
            tokens, targets = next(data)
            batch = (
                api.make_train_batch(cfg, jax.random.fold_in(key, step),
                                     args.batch, args.seq)
                if cfg.family in ("vlm", "audio")
                else {"tokens": tokens, "targets": targets}
            )
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            if layout and (step + 1) % args.cbtd_every == 0:
                alpha = alpha_at(step // args.cbtd_every, 0.2)
                params = prune(params, alpha)
            if (step + 1) % args.log_every == 0:
                print(f"step {step+1:5d} loss {float(metrics['loss']):.4f} "
                      f"({(time.time()-t0)/args.log_every:.2f}s/step)")
                t0 = time.time()
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, (params, opt_state),
                         {"step": step + 1, "data_step": data.step})
        if mgr:
            mgr.save(args.steps, (params, opt_state),
                     {"step": args.steps, "data_step": data.step})
            mgr.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
