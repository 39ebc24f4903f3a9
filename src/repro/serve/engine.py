"""Continuous-batching serving engine — the worker runtime behind FlowMesh's
data plane (the vLLM role in the paper, §4 "Containerized Workers"),
reimplemented TPU-native in JAX.

Adaptation (see DESIGN.md §3): instead of paged KV with pointer chasing, a
SLOT-BASED contiguous cache — (L, n_slots, H_kv, hd, max_len), positions
minor so hd 64/96 is not padded to 128 lanes, owned by the models — with a
free-slot allocator and per-slot valid lengths. Continuous batching = admit new
requests into free slots between decode steps; one jitted decode step always
runs over all slots (inactive slots are masked by their length), so the
compiled graph is static while the request mix churns — exactly the
"persistent executor with live admission queue" semantics of §3.1.
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

_req_ids = itertools.count()

#: JAX's monitoring event around each XLA program it makes, compiled or
#: loaded from the persistent compilation cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileCounter:
    """XLA programs made in this process: one listener on JAX's
    backend-compile event, registered once per process by the first
    engine. The count is process-wide: with four lanes in one process,
    each engine's ``compiles`` counts the programs of all four."""

    def __init__(self) -> None:
        self.n = 0
        self._lock = threading.Lock()
        self._registered = False

    def register(self) -> None:
        with self._lock:
            if self._registered:
                return
            self._registered = True
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_s: float, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.n += 1


_compiles = _CompileCounter()


@dataclass
class Request:
    prompt: np.ndarray                  # (T,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0            # 0 => greedy (deterministic -> CAS!)
    tenant: str = "default"
    req_id: int = field(default_factory=lambda: next(_req_ids))
    # filled by the engine:
    generated: list[int] = field(default_factory=list)
    slot: int | None = None
    done: bool = False


def _bucket(n: int) -> int:
    """Prefill compile-cache key. Exact length: right-padding a prefill is
    NOT semantics-preserving for recurrent families (padding tokens enter the
    SSM/conv state) and shifts the last-token logit for attention families.
    A production TPU deployment buckets lengths and corrects with masked-dt +
    conv-tail splicing; for this engine exact-length compiles are the simple,
    always-correct choice."""
    return n


class ServingEngine:
    """One persistent executor lane (one H_exec): weights stay resident,
    requests from any tenant stream through.

    Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock
    when a trace is on; one ``TraceMe`` check each when it is off), nested
    on the calling thread:

        engine.step      one ``step()``
        engine.admit     the admission loop of a step
        engine.prefill   one request's prefill, dispatch through its
                         first-token read (req_id, tokens, slot)
        engine.decode    the batched decode step's dispatch
        engine.sample    one token read, the body of ``_sample`` (req_id)
        engine.retire    one slot's length read and retirement (req_id)

    Counters, summed over the engine's life (``stats()``): ``steps``,
    ``tokens_generated``, ``prefills``, ``prompt_tokens``, ``host_reads``
    (each device-to-host read: one per sampled token, one per length
    check), ``prefill_s`` (host-clock seconds in ``engine.prefill``),
    ``decode_s`` (host-clock seconds of each step less its admission) and
    ``compiles`` (process-wide, see ``_CompileCounter``)."""

    def __init__(self, model, params, *, n_slots: int = 8,
                 max_len: int = 1024, seed: int = 0) -> None:
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        # the cache is committed to the weights' device: the donated cache
        # comes back committed, and the first call must see the same
        # placement as later ones or the prefill compiles twice
        device = next(iter(jax.tree.leaves(params)[0].devices()))
        self.cache = jax.device_put(model.init_cache(n_slots, max_len),
                                    device)
        self.free_slots = list(range(n_slots))
        self.active: dict[int, Request] = {}       # slot -> request
        self.waiting: list[Request] = []
        self.key = jax.random.key(seed)
        self.steps = 0
        self.tokens_generated = 0
        self.prefills = 0
        self.prompt_tokens = 0
        self.host_reads = 0
        self.prefill_s = 0.0
        self.decode_s = 0.0
        _compiles.register()

        # named so that a profile shows the module ``jit_engine_decode``
        def engine_decode(params, tokens, cache):
            return model.decode(params, tokens, cache)

        self._decode_step = jax.jit(engine_decode)
        self._prefill_cache: dict[int, Callable] = {}

    @property
    def compiles(self) -> int:
        """XLA programs made in this process so far (process-wide)."""
        return _compiles.n

    def stats(self) -> dict:
        """Every counter, for deltas around a window or a batch."""
        return {"steps": self.steps,
                "tokens_generated": self.tokens_generated,
                "prefills": self.prefills,
                "prompt_tokens": self.prompt_tokens,
                "host_reads": self.host_reads,
                "compiles": self.compiles,
                "prefill_s": self.prefill_s,
                "decode_s": self.decode_s}

    # ------------------------------------------------------------- admit --
    def submit(self, req: Request) -> int:
        self.waiting.append(req)
        return req.req_id

    def _prefill_fn(self, bucket_len: int) -> Callable:
        """Single-slot prefill, jitted per prompt-length bucket: computes the
        slot's KV/state on a batch-of-1 cache then scatters it into the big
        cache at the slot index. The slot is a traced argument, so one
        program serves every slot."""
        if bucket_len in self._prefill_cache:
            return self._prefill_cache[bucket_len]
        model = self.model

        def engine_prefill(params, cache, tokens, true_len, slot):
            mini = model.init_cache(1, self.max_len)
            logits, mini = model.prefill(params, {"tokens": tokens}, mini)
            # splice slot: every cache leaf has the slot axis right after
            # the (optional) layer axes; index map via tree of update fns
            def splice(big, small):
                if big.ndim == 0 or big.shape[-0:] == ():
                    return big
                # find the axis of size n_slots that small has as 1
                for ax in range(big.ndim):
                    if big.shape[ax] == self.n_slots and \
                            small.shape[ax] == 1:
                        idx = [0] * big.ndim
                        idx[ax] = slot
                        return jax.lax.dynamic_update_slice(
                            big, small.astype(big.dtype), tuple(idx))
                return big
            new_cache = jax.tree.map(splice, cache, mini)
            # correct the per-slot length to the TRUE prompt length (the
            # bucket padding contributes garbage KV beyond it, masked out)
            new_index = cache["index"].at[slot].set(true_len)
            new_cache["index"] = new_index
            return logits, new_cache

        jitted = jax.jit(engine_prefill, donate_argnums=(1,))
        self._prefill_cache[bucket_len] = jitted
        return jitted

    def _admit(self) -> None:
        with TraceAnnotation("engine.admit"):
            while self.waiting and self.free_slots:
                req = self.waiting.pop(0)
                slot = self.free_slots.pop(0)
                T = len(req.prompt)
                with TraceAnnotation("engine.prefill", req_id=req.req_id,
                                     tokens=T, slot=slot):
                    t0 = time.perf_counter()
                    toks = np.asarray(req.prompt, np.int32).reshape(1, T)
                    fn = self._prefill_fn(_bucket(T))
                    logits, self.cache = fn(self.params, self.cache,
                                            jnp.asarray(toks), T, slot)
                    first = self._sample(logits[0, -1], req)
                    req.generated.append(int(first))
                    self.prefill_s += time.perf_counter() - t0
                self.prefills += 1
                self.prompt_tokens += T
                req.slot = slot
                self.active[slot] = req

    # ------------------------------------------------------------- decode --
    def _decode(self, params, tokens: jax.Array, cache):
        with TraceAnnotation("engine.decode"):
            return self._decode_step(params, tokens, cache)

    def _sample(self, logits: jax.Array, req: Request) -> int:
        with TraceAnnotation("engine.sample", req_id=req.req_id):
            self.host_reads += 1
            if req.temperature <= 0.0:
                return int(jnp.argmax(logits))
            self.key, sub = jax.random.split(self.key)
            return int(jax.random.categorical(sub, logits / req.temperature))

    def _at_limit(self, req: Request, slot: int) -> bool:
        """Whether ``req`` is done: its token budget, else (one host read)
        its slot's cache length."""
        if len(req.generated) >= req.max_new_tokens:
            return True
        self.host_reads += 1
        return int(self.cache["index"][slot]) >= self.max_len - 1

    def step(self) -> list[Request]:
        """One engine iteration: admit -> one batched decode -> retire.
        Returns requests completed this step."""
        with TraceAnnotation("engine.step"):
            self._admit()
            if not self.active:
                return []
            t0 = time.perf_counter()
            toks = np.zeros((self.n_slots, 1), np.int32)
            for slot, req in self.active.items():
                toks[slot, 0] = req.generated[-1]
            logits, self.cache = self._decode(self.params, jnp.asarray(toks),
                                              self.cache)
            self.steps += 1
            finished = []
            for slot, req in list(self.active.items()):
                nxt = self._sample(logits[slot, -1], req)
                req.generated.append(nxt)
                self.tokens_generated += 1
                with TraceAnnotation("engine.retire", req_id=req.req_id):
                    if self._at_limit(req, slot):
                        req.done = True
                        finished.append(req)
                        del self.active[slot]
                        self.free_slots.append(slot)
            self.decode_s += time.perf_counter() - t0
            return finished

    def run(self, requests: list[Request]) -> list[Request]:
        """Serve a closed batch of requests to completion (test harness)."""
        for r in requests:
            self.submit(r)
        done: list[Request] = []
        while self.waiting or self.active:
            done.extend(self.step())
        return done
