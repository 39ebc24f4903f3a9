"""Kernel microbenchmarks. On this CPU container, Pallas kernels run in
interpret mode (Python semantics — NOT indicative of TPU wall-time), so the
numbers reported are the XLA-fallback timings at serving-typical shapes plus
a correctness cross-check. TPU-projected times come from the roofline terms
(see roofline_report).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ops import flash_attention, decode_attention, ssd_scan

from .common import csv_line


def _time(fn, *args, iters=3) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def main(fast: bool = False) -> list[str]:
    key = jax.random.key(0)
    lines = []

    # flash attention, serving-typical shape (XLA path on CPU)
    B, T, Hq, Hkv, hd = 1, 512, 8, 2, 64
    q = jax.random.normal(key, (B, T, Hq, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, hd))
    us = _time(lambda *a: flash_attention(*a, backend="xla"), q, k, v)
    lines.append(csv_line("kernel.flash_attention_xla", us,
                          f"B{B}xT{T}xH{Hq}x{hd};cpu-fallback"))

    # decode attention at 8k context
    S = 2048 if fast else 8192
    q1 = jax.random.normal(key, (4, Hq, hd), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(key, 3), (1, 4, Hkv, hd, S))
    vc = jax.random.normal(jax.random.fold_in(key, 4), (1, 4, Hkv, hd, S))
    lens = jnp.array([S, S // 2, S // 4, 100], jnp.int32)
    us = _time(lambda *a: decode_attention(*a, backend="xla"),
               q1, kc, vc, jnp.int32(0), lens)
    lines.append(csv_line("kernel.decode_attention_xla", us,
                          f"B4xS{S};ragged-lengths;cpu-fallback"))

    # ssd scan
    Bm_, T_, H_, P_, N_ = 1, 1024, 4, 64, 64
    u = jax.random.normal(key, (Bm_, T_, H_, P_), jnp.float32) * 0.3
    loga = -jax.random.uniform(jax.random.fold_in(key, 5), (Bm_, T_, H_))
    Bmat = jax.random.normal(jax.random.fold_in(key, 6), (Bm_, T_, N_)) * 0.3
    Cmat = jax.random.normal(jax.random.fold_in(key, 7), (Bm_, T_, N_)) * 0.3
    us = _time(lambda *a: ssd_scan(*a, backend="xla")[0], u, loga, Bmat, Cmat)
    lines.append(csv_line("kernel.ssd_scan_xla", us,
                          f"T{T_}xH{H_}xP{P_}xN{N_};sequential-oracle"))

    # interpret-mode correctness spot check (the pallas kernel itself)
    import numpy as np
    out_i = flash_attention(q[:, :64], k[:, :64], v[:, :64],
                            backend="interpret", blk_q=32, blk_k=32)
    out_r = ref.flash_attention_ref(q[:, :64], k[:, :64], v[:, :64],
                                    causal=True)
    err = float(jnp.max(jnp.abs(out_i - out_r)))
    lines.append(csv_line("kernel.pallas_interpret_check", 0.0,
                          f"max_err={err:.2e};ok={err < 1e-4}"))
    return lines


if __name__ == "__main__":
    for line in main():
        print(line)
