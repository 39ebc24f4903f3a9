"""Serving driver: a FlowMesh worker lane in miniature.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --requests 16 --max-new 12

Boots the continuous-batching engine for one H_exec (arch + params), streams
a batch of multi-tenant requests through it, and reports throughput and
the engine's counters (``ServingEngine.stats()``) — the same code path the
fabric's JaxExecutor drives.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models.transformer import build_model
from repro.serve.engine import Request, ServingEngine


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    if not hasattr(model, "prefill"):
        raise SystemExit(f"{args.arch}: family has no serving path")
    params = model.init(jax.random.key(args.seed))
    eng = ServingEngine(model, params, n_slots=args.slots,
                        max_len=args.max_len, seed=args.seed)

    rng = np.random.default_rng(args.seed)
    reqs = [Request(rng.integers(0, cfg.vocab_size,
                                 int(rng.integers(4, 24))).astype(np.int32),
                    max_new_tokens=args.max_new,
                    tenant=f"tenant-{i % 4}")
            for i in range(args.requests)]
    t0 = time.time()
    done = eng.run(reqs)
    dt = time.time() - t0
    result = {
        "requests": len(done),
        **eng.stats(),
        "wall_s": round(dt, 2),
        "tok_per_s": round(eng.tokens_generated / dt, 1),
        "tenants": sorted({r.tenant for r in done}),
    }
    print(f"[serve] {json.dumps(result)}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
