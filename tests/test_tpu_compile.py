"""Compile rehearsal for the TPU v5e at published widths, with no chip.

The TPU compiler is installed alongside JAX, so a program can be compiled
for a chip that is only described (``get_topology_desc``). That catches what
interpret-mode kernel tests cannot: tile alignment, fast-memory limits, and a
program that does not fit the device. Nothing runs, so nothing here says
anything about results or times.

This is the only test file that describes the chip. The topology is
described inside a module-scoped fixture — never at import, in a ``skipif``
or in ``parametrize`` — because only one process may load the TPU library,
and it keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models.transformer import build_model

#: one TPU v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


@pytest.fixture
def pallas_backend():
    ops.set_backend("pallas")
    try:
        yield
    finally:
        ops.set_backend(None)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    return compiled.as_text(), mem.argument_size_in_bytes \
        + mem.temp_size_in_bytes


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.usefixtures("no_persistent_cache")
def test_flash_attention_compiles_llama_widths(one_chip):
    """Prefill-length flash attention, llama-3.2-1b heads (32 q / 8 kv)."""
    q = _spec((1, 2048, 32, 64), jnp.bfloat16, one_chip)
    kv = _spec((1, 2048, 8, 64), jnp.bfloat16, one_chip)
    hlo, nbytes = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, kv, kv)
    assert "tpu_custom_call" in hlo
    assert nbytes < V5E_HBM_BYTES


@pytest.mark.usefixtures("no_persistent_cache")
@pytest.mark.parametrize("B,S,Hq,Hkv,hd", [
    (4, 256, 32, 8, 64),      # the served lane: 4 slots x 256 positions
    (8, 1024, 32, 8, 64),     # longer cache, more slots
    (4, 256, 32, 32, 96),     # phi3-mini heads: hd 96, one q head per kv
])
def test_decode_attention_compiles(one_chip, B, S, Hq, Hkv, hd):
    assert S % 256 == 0       # decode_attention's kv block
    q = _spec((B, Hq, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, Hkv, hd), jnp.bfloat16, one_chip)
    lengths = _spec((B,), jnp.int32, one_chip)
    hlo, nbytes = _compile(decode_attention, q, kv, kv, lengths)
    assert "tpu_custom_call" in hlo
    assert nbytes < V5E_HBM_BYTES


@pytest.mark.usefixtures("no_persistent_cache", "pallas_backend")
def test_llama_3_2_1b_decode_step_compiles(one_chip):
    """One whole decode step of the served model at its published widths,
    4 slots x 256 positions: the program a JAX worker lane runs per token."""
    cfg = get_config("llama-3.2-1b")
    model = build_model(cfg)
    place = lambda s: _spec(s.shape, s.dtype, one_chip)   # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(model.init,
                                                jax.random.key(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(4, 256)))
    tokens = _spec((4, 1), jnp.int32, one_chip)
    hlo, nbytes = _compile(model.decode, params, tokens, cache)
    assert "tpu_custom_call" in hlo
    assert nbytes < V5E_HBM_BYTES
