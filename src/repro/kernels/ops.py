"""Jit'd dispatch wrappers: Pallas kernel on TPU, interpret-mode Pallas or
pure-XLA reference elsewhere. Models call THESE, so flipping the backend is a
config knob, not a code change.

Policy resolution order:
  1. explicit ``backend=`` argument ("pallas" | "xla" | "interpret");
  2. module default set by ``set_backend`` (launch layer flips this);
  3. auto: "pallas" on TPU, "xla" otherwise (dry-run lowers the XLA path —
     TPU pallas_call cannot compile for the CPU host platform).
"""
from __future__ import annotations

import jax

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .decode_attention import mla_decode_attention as _mla_decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .ssd_scan import ssd_scan as _ssd_pallas

_DEFAULT: str | None = None


def set_backend(name: str | None) -> None:
    """name in {"pallas", "xla", "interpret", None=auto}."""
    global _DEFAULT
    _DEFAULT = name


def _resolve(backend: str | None) -> str:
    if backend is not None:
        return backend
    if _DEFAULT is not None:
        return _DEFAULT
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    backend: str | None = None, **kw):
    """v may be narrower than q/k; ``scale`` defaults to 1/sqrt(hd)."""
    be = _resolve(backend)
    if be == "xla":
        if q.shape[1] >= 1024:
            # flash-style chunked XLA lowering: no S^2 materialization
            from repro.models.attention import chunked_attention
            return chunked_attention(q, k, v, causal=causal, scale=scale)
        return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    return _flash_pallas(q, k, v, causal=causal, scale=scale,
                         interpret=(be == "interpret"), **kw)


def decode_attention(q, k_cache, v_cache, layer, lengths, *,
                     backend: str | None = None, **kw):
    """k_cache/v_cache: the stacked (L, B, Hkv, hd, S) cache; ``layer``
    picks the layer the query attends."""
    be = _resolve(backend)
    if be == "xla":
        return ref.decode_attention_ref(q, k_cache, v_cache, layer, lengths)
    return _decode_pallas(q, k_cache, v_cache, layer, lengths,
                          interpret=(be == "interpret"), **kw)


def mla_decode_attention(q, cache, layer, lengths, *, scale: float,
                         rank: int, backend: str | None = None, **kw):
    """cache: the stacked latent cache (L, B, rank + rope, S); ``layer``
    picks the layer the query attends."""
    be = _resolve(backend)
    if be == "xla":
        return ref.mla_decode_attention_ref(q, cache, layer, lengths,
                                            scale=scale, rank=rank)
    return _mla_decode_pallas(q, cache, layer, lengths, scale=scale,
                              rank=rank, interpret=(be == "interpret"), **kw)


def grouped_matmul(x, w, group_sizes):
    """Rows of ``x`` (m, k) in consecutive groups, group i of
    ``group_sizes[i]`` rows times ``w[i]`` (k, n); rows past the groups
    give zeros. XLA's ragged dot, which on the TPU is a kernel of its own
    (``ragged-dot`` in a trace), on every backend."""
    return jax.lax.ragged_dot(x, w, group_sizes)


def ssd_scan(u, loga, Bm, Cm, *, backend: str | None = None, **kw):
    be = _resolve(backend)
    if be == "xla":
        return ref.ssd_ref(u, loga, Bm, Cm)
    return _ssd_pallas(u, loga, Bm, Cm, interpret=(be == "interpret"), **kw)
