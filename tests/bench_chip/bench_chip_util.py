"""A throwaway checkout for the benchmark's tests: the repository's program
(``src/``, ``scripts/``) linked in, a copy of ``benchmarks/chip``, and a
BENCHMARK.json of tiny cells that run on the CPU in seconds. The tiny
configuration is added as files alone, as a later change would add one."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY = {
    "registry_id": "llama-3.2-1b",
    "program_overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                          "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                          "vocab_size": 256},
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_hidden_layers": 2,
               "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
               "rms_norm_eps": 1e-05, "rope_theta": 500000.0},
    "reduced": [], "assumed": [],
    "kernels": {"decode_attention": "decode_attention"},
    # at this width on the CPU the program reads 0.000-0.003 and the fp8
    # control 0.08-0.35 (eight seeds); planted faults read far above
    "check": {"logit_gap_limit": 0.03, "sample_requests": 4},
}

ENGINE_MIX = {"driver": "engine", "slots": 4, "max_len": 256, "batch": 8,
              "rounds": [[16, 32, 48, 64], [24, 40, 56, 72]],
              "new_tokens": 8, "max_batches": 400, "trace_seconds": 1}


def checkout(tmp: Path, *, metric_src: str | None = None) -> Path:
    """Build the checkout under ``tmp``; return its root."""
    root = tmp / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    for d in ("src", "scripts"):
        (root / d).symlink_to(REPO / d)
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-engine.json").write_text(
        json.dumps(ENGINE_MIX))
    per_layer = [
        {"name": "device_idle_share.tiny", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "tokens_per_s", "workloads": ["tiny.engine"]},
        {"name": "decode_step_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "serving engine",
         "moves": "tokens_per_s", "workloads": ["tiny.engine"]},
    ]
    if metric_src is not None:
        (bench / "metrics" / "steps_seen.py").write_text(metric_src)
        per_layer.append({"name": "steps_seen", "unit": "steps",
                          "better": "higher", "source": "host_clock",
                          "layer": "serving engine",
                          "moves": "tokens_per_s",
                          "workloads": ["tiny.engine"]})
    spec = {
        "command": ["python3", "benchmarks/chip/run.py"],
        "paths": ["benchmarks/chip"], "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "https://example.org/tiny",
                     "file": "benchmarks/chip/configs/tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "tiny.engine", "config": "tiny",
             "traffic": "tiny-engine", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny.engine"]}],
        "per_layer": per_layer,
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root: Path, cell: str, **kw) -> dict:
    """One run of a tiny cell on the CPU, the chip check skipped."""
    import time
    import run as bench_run
    kw.setdefault("seconds", 2.0)
    kw.setdefault("trace", False)
    seconds, trace = kw.pop("seconds"), kw.pop("trace")
    return bench_run.run_cell(cell, kw.pop("seed", 3_000_000_017),
                              seconds, trace, t_start=time.monotonic(),
                              root=root,
                              bench_dir=root / "benchmarks" / "chip",
                              require_tpu=False, **{"log": lambda *_: None, **kw})
