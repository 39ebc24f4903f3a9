"""Logical-axis activation sharding (MaxText-style).

Model code annotates activations with LOGICAL axis names
(``constrain(h, "batch", "seq", "embed")``); the launch layer installs a
policy mapping logical names -> mesh axes for the current mesh. With no
policy installed (CPU unit tests) the calls are no-ops, so model code stays
mesh-agnostic.

Why this exists: GSPMD propagation alone replicates the (batch, seq, vocab)
loss chain at 1M-token batches — the dry-run showed 627 GB/device temps on a
135M model before these constraints pinned batch/vocab sharding.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

_state = threading.local()


DEFAULT_RULES = {
    "batch": None,        # filled with ("pod","data")/("data",) at install
    "seq": None,
    "embed": None,
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "experts": "model",
    "state": None,
    "q_seq": "model",     # S^2 score tensors: query dim over model (XLA path)
    #: sequence-parallel residuals (training trunks): the per-layer saved
    #: activation stack shards its seq dim over "model" — 16x less residual
    #: HBM (kimi train: 57 GB -> 3.6 GB per device)
    "seq_res": "model",
}


def gspmd(mesh):
    """``mesh`` with every axis Auto. The models are written for GSPMD
    propagation steered by ``constrain``; ``jax.make_mesh`` types its axes
    Explicit, under which every op whose output layout is ambiguous raises."""
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def install(mesh, rules: dict[str, Any] | None = None) -> None:
    mesh = gspmd(mesh)
    r = dict(DEFAULT_RULES)
    r["batch"] = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if rules:
        r.update(rules)
    _state.mesh = mesh
    _state.rules = r


def clear() -> None:
    _state.mesh = None
    _state.rules = None


@contextlib.contextmanager
def policy(mesh, rules: dict[str, Any] | None = None):
    install(mesh, rules)
    try:
        yield
    finally:
        clear()


def active() -> bool:
    return getattr(_state, "mesh", None) is not None


def current_mesh():
    return getattr(_state, "mesh", None)


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Pin x's sharding by logical axis names (None = replicated dim)."""
    mesh = getattr(_state, "mesh", None)
    if mesh is None:
        return x
    rules = _state.rules
    spec = P(*(rules.get(a) if a else None for a in logical_axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
