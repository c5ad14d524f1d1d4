"""End-to-end training driver with checkpoint/restart fault tolerance.

    PYTHONPATH=src python -m repro.launch.train \
        --arch tinyllama-1.1b --reduced --steps 50 --batch 8 --seq 128 \
        --checkpoint-dir /tmp/ckpt --save-every 10

Restart semantics: on startup the latest checkpoint in --checkpoint-dir is
restored and the data pipeline is fast-forwarded to the restored step, so a
killed run resumes bit-exactly (the data pipeline is a pure function of
(seed, step)).  ``--fault-at N`` injects a crash at step N to demonstrate.
"""
from __future__ import annotations

import argparse
import time


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="tinyllama-1.1b")
    p.add_argument("--reduced", action="store_true",
                   help="reduced same-family config (CPU-sized)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--optimizer", default="adamw", choices=["adamw", "adafactor"])
    p.add_argument("--microbatch", type=int, default=1)
    p.add_argument("--remat", default="selective")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--grad-compress", action="store_true")
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--save-every", type=int, default=20)
    p.add_argument("--keep", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--fault-at", type=int, default=-1,
                   help="inject a crash at this step (fault-tolerance demo)")
    p.add_argument("--log-every", type=int, default=10)
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch, reduced
    from repro.data import DataConfig, SyntheticLM, device_put_batch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import BuildFlags, Model
    from repro.parallel.sharding import ShardingPolicy
    from repro.train import (CheckpointManager, TrainStepConfig, adafactor,
                             adamw, cosine_schedule, init_train_state,
                             make_train_step)

    enable_compile_cache()
    arch = get_arch(args.arch)
    if args.reduced:
        arch = reduced(arch)
    mesh = make_host_mesh()
    policy = ShardingPolicy(mesh, sp=False) if mesh.size > 1 else None
    flags = BuildFlags(dtype=args.dtype, remat=args.remat, sp=False)
    model = Model(arch, flags, policy)
    sched = cosine_schedule(args.lr, max(args.steps // 20, 1), args.steps)
    opt = adafactor(sched) if args.optimizer == "adafactor" else adamw(sched)
    tsc = TrainStepConfig(microbatch=args.microbatch,
                          grad_compress=args.grad_compress)
    step_fn = jax.jit(make_train_step(model, opt, tsc), donate_argnums=(0,))

    state = init_train_state(model, opt, jax.random.key(args.seed), tsc)
    start = 0
    ck = None
    if args.checkpoint_dir:
        ck = CheckpointManager(args.checkpoint_dir, keep=args.keep)
        latest = ck.latest_step()
        if latest is not None:
            state = ck.restore(latest, jax.eval_shape(lambda: state))
            start = latest
            print(f"[train] resumed from step {start}")

    data = SyntheticLM(arch, DataConfig(args.batch, args.seq, args.seed))
    t0 = time.time()
    for step in range(start, args.steps):
        if step == args.fault_at:
            if ck:
                # crash at a step boundary with in-flight checkpoint IO
                # drained — mid-write crashes are separately survivable via
                # the tmp+rename atomic publish (restore ignores .tmp dirs)
                ck.wait()
            print(f"[train] injected fault at step {step}", flush=True)
            raise SystemExit(42)
        batch = device_put_batch(data.batch(step), policy)
        state, metrics = step_fn(state, batch)
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            loss = float(metrics["loss"])
            print(f"[train] step {step+1:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0)/(step-start+1)*1e3:.0f} ms/step)", flush=True)
        if ck and (step + 1) % args.save_every == 0:
            ck.save(step + 1, state)
    if ck:
        ck.save(args.steps, state, block=True)
        ck.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
