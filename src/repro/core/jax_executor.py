"""JaxExecutor: the fabric's workers running REAL JAX compute.

The virtual-time SimExecutor answers "what would this cost on an H100 fleet";
this executor actually runs the operators — generation through the
continuous-batching ServingEngine, SFT/DPO/PPO through the training substrate
— for one architecture, at whatever widths the caller's config gives, on one
JAX device. Durations are measured wall-clock, outputs are deterministic
functions of the inputs (greedy decode, seeded data), so dedup/speculation/
CAS semantics hold bit-exactly.

One executor is one lane's runtime: its weights, serving engine and jitted
calls live on its own device (``scripts/worker_main.py`` runs one per chip).
"""
from __future__ import annotations

import math
import pickle
import time

from .identity import digest

import jax
import numpy as np

from repro.models.common import ArchConfig
from repro.models.transformer import build_model
from repro.serve.engine import Request, ServingEngine
from repro.train.data import DataConfig, SyntheticLM
from repro.train.optimizer import OptimizerConfig, build_optimizer
from repro.train.train_step import build_train_step, init_train_state

from .dag import OpType
from .worker import DispatchBatch, ExecResult, Executor, Worker

_TRAIN_OPS = (OpType.SFT, OpType.DPO, OpType.PPO)
#: the serving engine's slots x positions (decode_attention needs
#: max_len % 256 == 0)
N_SLOTS, MAX_LEN = 4, 256


def _nbytes(tree) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


class JaxExecutor(Executor):
    def __init__(self, cfg: ArchConfig, *, device=None, seed: int = 0,
                 train_steps_per_op: int = 3, gen_tokens: int = 8) -> None:
        self.cfg = cfg
        self.device = device if device is not None else jax.devices()[0]
        self.model = build_model(cfg)
        self.train_steps_per_op = train_steps_per_op
        self.gen_tokens = gen_tokens
        self.opt = build_optimizer(OptimizerConfig(peak_lr=1e-3, warmup=2))
        self._train_step = jax.jit(build_train_step(self.model, self.opt))
        with jax.default_device(self.device):
            self.params = jax.device_put(
                jax.jit(self.model.init)(jax.random.key(seed)), self.device)
            self.engine = ServingEngine(self.model, self.params,
                                        n_slots=N_SLOTS, max_len=MAX_LEN,
                                        seed=seed)

    def placement(self) -> list[int]:
        leaves = jax.tree.leaves((self.params, self.engine.cache))
        return sorted({d.id for x in leaves for d in x.devices()})

    # ------------------------------------------------------------------
    def prompt_for(self, hashes: tuple[str, ...], length: int = 12):
        """The prompt an operator with these inputs generates from."""
        # stable across processes (python's hash() is randomized)
        seed = int(digest("prompt", *hashes)[:8], 16)
        rng = np.random.default_rng(seed)
        return rng.integers(0, self.cfg.vocab_size, length).astype(np.int32)

    def _train_refusal(self) -> str | None:
        """Why a training op cannot run on this device, or None. Weights,
        gradients and AdamW's f32 moments must fit beside the resident
        serving weights; a device that reports no memory limit (the host
        CPU) is not checked."""
        limit = (self.device.memory_stats() or {}).get("bytes_limit")
        if not limit:
            return None
        state = jax.eval_shape(
            lambda: init_train_state(self.model, self.opt,
                                     jax.random.key(0)))
        need = _nbytes(self.params) + _nbytes(state) \
            + _nbytes(state["params"])                  # gradients
        if need <= limit:
            return None
        return (f"train_state_exceeds_device_memory: {self.cfg.name} needs "
                f"{need / 1e9:.1f} GB of {limit / 1e9:.1f} GB")

    # ------------------------------------------------------------------
    def execute(self, batch: DispatchBatch, worker: Worker, cas) -> ExecResult:
        t0 = time.perf_counter()
        spec = batch.groups[0].spec
        failure = None
        if spec.model_id and spec.model_id != self.cfg.name:
            failure = (f"model_mismatch: lane serves {self.cfg.name}, "
                       f"op wants {spec.model_id}")
        elif spec.op_type in _TRAIN_OPS:
            failure = self._train_refusal()
        if failure is not None:
            return ExecResult(outputs=[], duration_s=0.0, load_s=0.0,
                              failed=True, failure=failure)
        with jax.default_device(self.device):
            outputs = self._run(batch, spec)
        dur = time.perf_counter() - t0
        # the lane's weights are resident from construction: nothing loads
        return ExecResult(outputs=outputs, duration_s=dur, load_s=0.0,
                          flops=0.0)

    def _run(self, batch: DispatchBatch, spec) -> list[bytes]:
        outputs = []
        if spec.op_type in (OpType.GENERATE, OpType.SCORE, OpType.EVAL):
            reqs = [Request(self.prompt_for(g.input_hashes),
                            max_new_tokens=self.gen_tokens, temperature=0.0)
                    for g in batch.groups]
            done = {r.req_id: r for r in self.engine.run(list(reqs))}
            for r in reqs:
                outputs.append(pickle.dumps(
                    {"op": spec.op_type.value,
                     "tokens": done[r.req_id].generated}))
        elif spec.op_type in _TRAIN_OPS:
            state = init_train_state(self.model, self.opt, jax.random.key(1))
            data = SyntheticLM(DataConfig(
                self.cfg.vocab_size, 32, 4,
                seed=int(digest("data", spec.name)[:6], 16)))
            losses = []
            for i in range(self.train_steps_per_op):
                state, m = self._train_step(state, data.batch(i))
                losses.append(float(m["loss"]))
            for g in batch.groups:
                outputs.append(pickle.dumps(
                    {"op": spec.op_type.value, "losses": losses,
                     "inputs": g.input_hashes}))
        else:
            # TOOL / DATA_PREP / AGGREGATE: a deterministic transform of the
            # inputs' content addresses — a remote lane holds no CAS handle
            for g in batch.groups:
                outputs.append(pickle.dumps(
                    {"op": spec.op_type.value,
                     "inputs": list(g.input_hashes)}))
        return outputs
