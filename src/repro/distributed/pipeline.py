"""GPipe-style pipeline parallelism over a "stage" mesh axis (shard_map +
collective_permute).

Optional feature for depth-dominated models at pod scale: stages hold
contiguous layer slices; microbatches stream through the classic GPipe
schedule (n_micro + n_stages - 1 ticks); activations hop stages via
jax.lax.ppermute. Bubble fraction = (S-1)/(S-1+M).

The implementation is deliberately family-agnostic: it pipelines any
``stage_fn(stage_params, x) -> x`` over stacked per-stage params, so tests
verify it against the sequential model bit-for-bit.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline(stage_fn: Callable, mesh: Mesh, *, axis: str = "stage",
             n_microbatches: int) -> Callable:
    """Returns f(stage_params, x) -> y running the GPipe schedule.

    stage_params: pytree with leading axis == n_stages (sharded over
    ``axis``); x: (n_microbatches, mb, ...) replicated input; returns
    (n_microbatches, mb, ...) output of the LAST stage.
    """
    S = mesh.shape[axis]
    M = n_microbatches

    def run(stage_params, x):
        # inside shard_map: stage_params has leading dim 1 (this stage)
        local = jax.tree.map(lambda p: p[0], stage_params)
        i = jax.lax.axis_index(axis)
        mb_shape = x.shape[1:]

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 ingests microbatch t; others use what arrived last tick
            feed = jnp.where(t < M, t, 0)
            x_in = jnp.where(i == 0, x[feed], inflight)
            y = stage_fn(local, x_in)
            # results leaving the last stage at tick t correspond to
            # microbatch t - (S-1)
            out_idx = t - (S - 1)
            valid = (i == S - 1) & (out_idx >= 0) & (out_idx < M)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (jnp.maximum(out_idx, 0),)
                    + (0,) * len(mb_shape)),
                lambda o: o, outputs)
            # hop to the next stage (ring; the wraparound value is unused)
            nxt = jax.lax.ppermute(
                y, axis, [(s, (s + 1) % S) for s in range(S)])
            return (nxt, outputs), None

        init = (jnp.zeros(mb_shape, x.dtype),
                jnp.zeros((M,) + mb_shape, x.dtype))
        (_, outputs), _ = jax.lax.scan(
            tick, init, jnp.arange(M + S - 1))
        # every stage computed `outputs`, only the last stage's is real;
        # broadcast it (tiny for loss-sized outputs; callers that keep
        # activations should shard instead)
        outputs = jax.lax.psum(
            jnp.where(i == S - 1, outputs, jnp.zeros_like(outputs)), axis)
        return outputs

    in_specs = (P(axis), P())
    out_specs = P()
    return jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def split_stages(layer_params, n_stages: int):
    """Reshape stacked (L, ...) layer params into (n_stages, L/S, ...)."""
    def r(p):
        L = p.shape[0]
        assert L % n_stages == 0, f"{L} layers not divisible by {n_stages}"
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])
    return jax.tree.map(r, layer_params)
