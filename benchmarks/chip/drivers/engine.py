"""Engine cells: one ``ServingEngine`` at the traffic's slots and cache
length, driven through ``submit``/``step`` in this process, which holds
the chip.

Set-up: JAX, the compile cache in the checkout, weights made on the chip
in one jitted call from the seed in the served dtype, then warm-up of the
cell's own shapes only (one prefill per prompt length of the mix, and the
decode step with every slot busy) on prompts drawn apart from the
window's. The window: closed batches back to back, each step timed on the
host clock (a step ends in host reads of the sampled tokens, so the device
has finished). Afterwards the program's state is freed and the float32
reference reads a seeded sample of the finished requests, the longest
prompt among them. The reference is the module the configuration file
names (``"reference": "<module>"``, ``reference/<module>.py``), loaded
before set-up; its ``WIDTHS`` are the widths the program is checked on.
With ``control`` the fp8 control stands in the program's place: the gap
check reads the tokens the control puts first at the same positions, and
a sound benchmark finds it not correct.
"""
from __future__ import annotations

import gc
import json
import random
import time

import schedule
import suite
from suite import NoChip, RunError
from program import break_engine, program_config, use_checkout

TRACE_SPANS = ("_admit", "_sample", "_decode")


def _annotate(engine) -> None:
    """``bench.*`` host spans around the engine's admission, sampling and
    decode calls (traced runs only)."""
    import jax
    for name in TRACE_SPANS:
        orig = getattr(engine, name, None)
        if orig is None:
            continue

        def wrapped(*a, _orig=orig, _span=f"bench{name.replace('_', '.')}",
                    **k):
            with jax.profiler.TraceAnnotation(_span):
                return _orig(*a, **k)
        setattr(engine, name, wrapped)


def run(cell: suite.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_tpu: bool = True, fault: str = "",
        control: bool = False, log=print) -> dict:
    use_checkout(cell.root)
    reference = suite.reference(cell)
    phases: dict[str, float] = {}
    import jax
    phases["import_jax"] = time.monotonic() - t_start
    if require_tpu:
        # the compile cache lives in the checkout, at a fixed path
        jax.config.update("jax_compilation_cache_dir",
                          str(cell.root / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    devices = jax.devices()
    dev = devices[0]
    phases["devices"] = time.monotonic() - t_start
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r}, kind "
                     f"{dev.device_kind!r})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX sees "
                     f"{len(devices)}")
    from repro.kernels import ops
    from repro.models.transformer import build_model
    from repro.serve.engine import Request, ServingEngine
    if require_tpu and ops._resolve(None) != "pallas":
        raise RunError("the engine fell back from the Pallas kernels")
    tr, config = cell.traffic, cell.config
    cfg = program_config(config, reference)
    model = build_model(cfg)
    with jax.default_device(dev):
        params = jax.jit(model.init)(jax.random.key(seed))
        jax.block_until_ready(params)
        phases["weights"] = time.monotonic() - t_start
        engine = ServingEngine(model, params, n_slots=int(tr["slots"]),
                               max_len=int(tr["max_len"]), seed=0)
        break_engine(engine, fault)
        vocab = cfg.vocab_size
        # warm-up: every prompt length once, then one round with every
        # slot busy; prompts from a stream the window never draws
        warm = schedule.eval_batches(
            {**tr, "batch": sum(len(r) for r in tr["rounds"])}, vocab,
            seed, 1, "warm")[0]
        engine.run([Request(r["prompt"], max_new_tokens=3) for r in warm])
        phases["warmed"] = time.monotonic() - t_start
        if trace:
            _annotate(engine)
        peak = suite.peaks(dev.device_kind, cell.bench_dir) \
            if require_tpu else None
        batches = schedule.eval_batches(tr, vocab, seed,
                                        int(tr.get("max_batches", 8)))
        steps: list[dict] = []
        finished: list = []
        trace_dir = None
        t0 = time.monotonic()
        setup_s = t0 - t_start
        t_stop = t0 + seconds
        trace_s = min(float(tr.get("trace_seconds", seconds)), seconds)
        # the trace covers the window's last trace_s seconds, so writing it
        # out (seconds) falls after the window
        traced = [None, None]
        if trace:
            import tempfile
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            win_span = jax.profiler.TraceAnnotation("bench.window")
        out_of_time = False
        for batch in batches:
            reqs = [Request(r["prompt"], max_new_tokens=r["max_new_tokens"])
                    for r in batch]
            for r in reqs:
                engine.submit(r)
            while engine.waiting or engine.active:
                if time.monotonic() >= t_stop:
                    out_of_time = True
                    break
                if trace and traced[0] is None and \
                        time.monotonic() >= t_stop - trace_s:
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
                    win_span.__enter__()
                    traced[0] = len(steps)
                waiting = list(engine.waiting)
                gen0 = engine.tokens_generated
                ts = time.monotonic()
                with jax.profiler.TraceAnnotation("bench.step"):
                    done = engine.step()
                te = time.monotonic()
                admitted = [r for r in waiting if r.slot is not None]
                live = list(engine.active.values()) + done
                steps.append({
                    "t": te, "dt": te - ts,
                    "prompt_tokens": sum(len(r.prompt) for r in admitted),
                    "prompts": [len(r.prompt) for r in admitted],
                    "admitted": len(admitted),
                    "generated": engine.tokens_generated - gen0
                    + len(admitted),
                    "lengths": [len(r.prompt) + len(r.generated) - 1
                                for r in live]})
                finished.extend(done)
            if out_of_time:
                break
        t_end = steps[-1]["t"] if steps else time.monotonic()
        if trace and traced[0] is not None:
            win_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced[1] = len(steps)
        if not out_of_time:
            raise RunError("the window outlasted the mix's batches: raise "
                           "max_batches in the traffic file")
        mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        reduced = None
        if trace:
            import shutil
            from trace_reduce import load_xplane, reduce
            reduced = reduce(load_xplane(trace_dir, [dev.id]),
                             config.get("kernels", {}))
            shutil.rmtree(trace_dir, ignore_errors=True)
        del engine, params
        gc.collect()
        jax.clear_caches()
        items = sample(finished, int(config["check"]["sample_requests"]),
                       seed)
        ref = reference.gaps(config, seed, items, control=control)
    window_s = t_end - t0
    tokens = window_tokens(steps)
    log("setup " + json.dumps({**phases, "window_open": setup_s}))
    log("window " + json.dumps({
        "steps": len(steps), "finished": len(finished),
        "window_s": window_s, "tokens": tokens,
        "prompt_tokens": sum(s["prompt_tokens"] for s in steps)}))
    gap = ref["gap_max"]
    log("gaps " + json.dumps({k: v for k, v in ref.items()
                              if k != "gap_per_item"}))
    if control:
        gap = ref["control_gap_max"]
    checks = [suite.check("finished_requests_missing",
                          0 if finished else 1, 0),
              suite.check("logit_gap_max", gap,
                          float(config["check"]["logit_gap_limit"]))]
    rec = {"kind": "engine", "window_s": window_s, "steps": steps,
           "traced_steps": traced, "trace": reduced,
           "peak": peak, "config": config}
    return {"rec": rec, "e2e": {"setup_s": setup_s,
                                "tokens_per_s": tokens / window_s},
            "checks": checks, "attempted": len(finished),
            "failed": 0, "ref": ref,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices), "memory_peak_bytes": mem_peak}}


def window_tokens(steps: list[dict]) -> int:
    """Prompt tokens prefilled plus tokens generated by the window's
    steps: the numerator of ``tokens_per_s``."""
    return sum(s["prompt_tokens"] + s["generated"] for s in steps)


def sample(finished: list, n: int, seed: int) -> list[dict]:
    """A seeded sample of finished requests, the longest prompt first."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt))
    rest = [r for r in finished if r is not longest]
    rng = random.Random(f"sample/{seed}")
    pick = [longest] + rng.sample(rest, min(n - 1, len(rest)))
    return [{"prompt": [int(t) for t in r.prompt],
             "served": [int(t) for t in r.generated]} for r in pick]

