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


def flash_attention(q, k, v, *, causal: bool = True, backend: str | None = None,
                    **kw):
    be = _resolve(backend)
    if be == "xla":
        if q.shape[1] >= 1024:
            # flash-style chunked XLA lowering: no S^2 materialization
            from repro.models.attention import chunked_attention
            return chunked_attention(q, k, v, causal=causal)
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash_pallas(q, k, v, causal=causal,
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


def ssd_scan(u, loga, Bm, Cm, *, backend: str | None = None, **kw):
    be = _resolve(backend)
    if be == "xla":
        return ref.ssd_ref(u, loga, Bm, Cm)
    return _ssd_pallas(u, loga, Bm, Cm, interpret=(be == "interpret"), **kw)
