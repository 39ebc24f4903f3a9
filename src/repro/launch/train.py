"""End-to-end training driver with CAS-backed checkpoint/restart and elastic
re-meshing.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-every 50

Fault tolerance contract (exercised by tests/test_launch_train.py and
examples/train_e2e.py):
  * every --ckpt-every steps the full train state is content-addressed into
    the CAS (incremental: unchanged leaves cost nothing);
  * --resume restarts from the latest manifest and replays the SAME data
    stream (the pipeline is a pure function of step) => bitwise-identical
    trajectory to an uninterrupted run;
  * on a different device count (elastic re-mesh after node loss), the state
    is resharded by device_put — training continues with identical math.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.cas import DiskCAS
from repro.models.transformer import build_model
from repro.train.checkpoint import Checkpointer
from repro.train.data import DataConfig, SyntheticLM
from repro.train.optimizer import OptimizerConfig, build_optimizer
from repro.train.train_step import build_train_step, init_train_state


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--cas", default="/tmp/flowmesh-cas")
    ap.add_argument("--run-name", default="train-e2e")
    ap.add_argument("--resume", default=None,
                    help="manifest hash to resume from ('latest' works)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(peak_lr=args.lr, warmup=20,
                              total_steps=max(args.steps, 100))
    opt = build_optimizer(opt_cfg)
    data = SyntheticLM(DataConfig(cfg.vocab_size, args.seq, args.batch,
                                  seed=args.seed))
    cas = DiskCAS(args.cas)
    ckpt = Checkpointer(cas, args.run_name)

    start_step = 0
    if args.resume:
        mh = None if args.resume == "latest" else args.resume
        state, start_step, extra = ckpt.restore(mh)
        print(f"[train] resumed from step {start_step} "
              f"(manifest {ckpt.latest or mh})")
    else:
        state = init_train_state(model, opt, jax.random.key(args.seed))

    step_fn = jax.jit(build_train_step(model, opt,
                                       grad_accum=args.grad_accum),
                      donate_argnums=(0,))
    losses = []
    t0 = time.time()
    last_manifest = None
    for i in range(start_step, args.steps):
        state, m = step_fn(state, data.batch(i))
        losses.append(float(m["loss"]))
        if args.log_every and (i + 1) % args.log_every == 0:
            rate = (i + 1 - start_step) / (time.time() - t0)
            print(f"[train] step {i + 1:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} "
                  f"({rate:.1f} steps/s)")
        if args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            last_manifest = ckpt.save(state, step=i + 1,
                                      extra={"arch": args.arch})
            print(f"[train] checkpoint @ {i + 1}: {last_manifest} "
                  f"({cas.bytes_written / 1e6:.1f} MB in CAS)")
    result = {
        "first_loss": losses[0] if losses else None,
        "final_loss": float(np.mean(losses[-10:])) if losses else None,
        "steps": args.steps,
        "manifest": last_manifest,
        "converged": bool(losses and np.mean(losses[-10:])
                          < losses[0] - 0.2),
    }
    print(f"[train] done: {json.dumps(result)}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
