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
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import (decode_attention,
                                            mla_decode_attention)
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
@pytest.mark.parametrize("L,B,S,Hq,Hkv,hd", [
    (16, 4, 256, 32, 8, 64),      # the served lane: 4 slots x 256 positions
    (16, 8, 1024, 32, 8, 64),     # longer cache, more slots
    (32, 4, 256, 32, 32, 96),     # phi3-mini heads: hd 96, one q head per kv
])
def test_decode_attention_compiles(one_chip, L, B, S, Hq, Hkv, hd):
    assert S % 256 == 0       # decode_attention's kv block
    q = _spec((B, Hq, hd), jnp.bfloat16, one_chip)
    kv = _spec((L, B, Hkv, hd, S), jnp.bfloat16, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    lengths = _spec((B,), jnp.int32, one_chip)
    hlo, nbytes = _compile(decode_attention, q, kv, kv, layer, lengths)
    assert "tpu_custom_call" in hlo
    assert nbytes < V5E_HBM_BYTES


@pytest.mark.usefixtures("no_persistent_cache")
def test_mla_decode_attention_compiles_kimi_widths(one_chip):
    """The MLA decode kernel at kimi-k2-chip's widths and the benchmark
    cell's cache: 9 layers x 4 slots x 16640 positions of 512 + 64
    latent values, 64 heads."""
    q = _spec((4, 64, 576), jnp.bfloat16, one_chip)
    cache = _spec((9, 4, 576, 16640), jnp.bfloat16, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    lengths = _spec((4,), jnp.int32, one_chip)
    hlo, nbytes = _compile(
        lambda q, c, l, n: mla_decode_attention(q, c, l, n, scale=0.13,
                                                rank=512),
        q, cache, layer, lengths)
    assert "tpu_custom_call" in hlo
    assert nbytes < V5E_HBM_BYTES


def _decode_step(one_chip, arch, B, S):
    """One whole decode step of ``arch`` at its published widths, B slots x
    S positions, compiled for one described chip."""
    model = build_model(get_config(arch))
    place = lambda s: _spec(s.shape, s.dtype, one_chip)   # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(model.init,
                                                jax.random.key(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(B, S)))
    tokens = _spec((B, 1), jnp.int32, one_chip)
    return jax.jit(model.decode).lower(params, tokens, cache).compile()


@pytest.mark.usefixtures("no_persistent_cache", "pallas_backend")
def test_llama_3_2_1b_decode_step_compiles(one_chip):
    """One whole decode step of the served model at its published widths,
    4 slots x 256 positions: the program a JAX worker lane runs per token."""
    compiled = _decode_step(one_chip, "llama-3.2-1b", 4, 256)
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


# -- what the compiled step does with the KV cache ---------------------------
#: opcodes that produce no buffer of their own
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
              "constant", "after-all", "call", "conditional"}


def _instructions(hlo: str) -> tuple[dict, str]:
    """Computations of compiled HLO text -> their instructions
    (name, opcode, element count of each result buffer, ROOT flag), and
    the entry computation's name."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY\s+)?%(\S+)\s.*\{$", line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        ins = re.match(r"^\s+(ROOT\s+)?%(\S+)\s*=\s*(.*)$", line)
        if not ins or cur is None:
            continue
        rhs = ins.group(3)
        if rhs.startswith("("):             # tuple shape: to its close
            depth = 0
            for end, ch in enumerate(rhs):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            shape, rest = rhs[:end + 1], rhs[end + 1:].lstrip()
        else:
            shape, _, rest = rhs.partition(" ")
        sizes = [math.prod(int(d) for d in dims.split(",") if d)
                 for dims in re.findall(r"\w+\[([\d,]*)\]", shape)]
        cur.append({"name": ins.group(2), "op": rest.split("(", 1)[0],
                    "sizes": sizes, "root": bool(ins.group(1)),
                    "text": rhs})
    return comps, entry


def _buffers(comps: dict, comp: str, n: int) -> list[str]:
    """Instructions of ``comp`` that materialise a buffer of ``n``
    elements. An update in place (a dynamic-update-slice, or a fusion
    whose root is one) writes into its operand's buffer and is not one."""
    found = []
    for ins in comps[comp]:
        if ins["op"] in _NO_BUFFER or n not in ins["sizes"] or \
                ins["op"] == "dynamic-update-slice":
            continue
        if ins["op"] == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", ins["text"]).group(1)
            if any(i["root"] and i["op"] == "dynamic-update-slice"
                   for i in comps[called]):
                continue
        found.append(f"{ins['name']} ({ins['op']})")
    return found


@pytest.mark.usefixtures("no_persistent_cache", "pallas_backend")
def test_phi3_mini_decode_step_keeps_the_cache_in_place(one_chip):
    """The ``phi3-mini.engine-eval`` decode step (phi3-mini at published
    widths, 4 slots x 2304 positions) carries the stacked cache through
    the layer scan: no op in the loop body materialises one layer's K or V
    (a slice, a transpose, a re-stack), the only whole-cache buffers are
    the K and V copies of the undonated call, and temporaries stay small."""
    cfg = get_config("phi3-mini-3.8b")
    B, S = 4, 2304
    compiled = _decode_step(one_chip, "phi3-mini-3.8b", B, S)
    comps, entry = _instructions(compiled.as_text())
    bodies = [re.search(r"body=%([\w.\-]+)", i["text"]).group(1)
              for i in comps[entry] if i["op"] == "while"]
    assert len(bodies) == 1                     # the layer scan
    layer_kv = B * cfg.n_kv_heads * cfg.hd * S
    assert _buffers(comps, bodies[0], layer_kv) == []
    whole = _buffers(comps, entry, cfg.n_layers * layer_kv) + \
        _buffers(comps, bodies[0], cfg.n_layers * layer_kv)
    assert len(whole) <= 2, whole
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    kernels = [i for i in comps[bodies[0]] if i["op"] == "custom-call"
               and "decode_attention" in i["name"]]
    assert len(kernels) == 1                    # one call per layer


@pytest.mark.usefixtures("no_persistent_cache", "pallas_backend")
def test_kimi_chip_decode_step_keeps_the_latent_cache_in_place(one_chip):
    """The ``kimi-k2.engine-longprompt`` decode step (kimi-k2-chip at
    published widths, 4 slots x 16640 positions) carries the stacked latent
    cache through the dense layer and the MoE layers' scan: no op
    materialises one layer's latent cache, the only whole-cache buffer is
    the undonated call's copy, each layer calls the MLA kernel once, and
    the held experts run XLA's ragged dot."""
    cfg = get_config("kimi-k2-chip")
    B, S = 4, 16640
    compiled = _decode_step(one_chip, "kimi-k2-chip", B, S)
    comps, entry = _instructions(compiled.as_text())
    bodies = [re.search(r"body=%([\w.\-]+)", i["text"]).group(1)
              for i in comps[entry] if i["op"] == "while"]
    # the MoE layers' scan; XLA unrolls the dense layer's scan of one
    assert len(bodies) == 1
    layer = B * (cfg.kv_lora_rank + cfg.qk_rope_dim) * S
    whole = []
    for comp, layers in ((entry, cfg.n_dense_layers),
                         (bodies[0], 1)):
        assert _buffers(comps, comp, layer) == []
        whole += _buffers(comps, comp, cfg.n_layers * layer)
        kernels = [i for i in comps[comp] if i["op"] == "custom-call"
                   and "mla_decode_attention" in i["name"]]
        assert len(kernels) == layers
    assert len(whole) <= 1, whole
    assert "ragged-dot" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM_BYTES
