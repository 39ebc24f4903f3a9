"""Continuous batching demo: multi-tenant requests stream into one
persistent executor lane (one H_exec); new requests join free slots while
others are mid-decode — the worker runtime of the paper's data plane.

    PYTHONPATH=src python examples/serve_continuous_batching.py
"""
import numpy as np

import jax
from repro.configs import get_config
from repro.models.transformer import build_model
from repro.serve.engine import Request, ServingEngine


def main():
    cfg = get_config("smollm-135m").reduced(n_layers=2, d_model=64,
                                            vocab_size=128, d_ff=128)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServingEngine(model, params, n_slots=3, max_len=128)
    rng = np.random.default_rng(0)

    # tenants A/B submit; C arrives mid-flight and is admitted into a slot
    first = [Request(rng.integers(0, 128, 6).astype(np.int32),
                     max_new_tokens=10, tenant=t) for t in "AAB"]
    for r in first:
        eng.submit(r)
    done = []
    for step in range(4):
        done += eng.step()
    late = Request(rng.integers(0, 128, 5).astype(np.int32),
                   max_new_tokens=6, tenant="C")
    eng.submit(late)
    print(f"slots busy when C arrived: {len(eng.active)} of {eng.n_slots}")
    while eng.waiting or eng.active:
        done += eng.step()
    print("== continuous batching ==")
    for r in sorted(done, key=lambda r: r.req_id):
        print(f"  tenant {r.tenant} req{r.req_id}: {len(r.generated)} tokens "
              f"-> {r.generated[:6]}...")
    assert len(done) == 4 and all(r.done for r in done)
    print(f"engine: {eng.stats()}")
    print("OK")


if __name__ == "__main__":
    main()
