"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics come from BENCHMARK.json
and the files beside this one (see ``suite.py``). Earlier lines of standard
output report set-up by phase, how late the load generator ran and the
window's counts; the last line is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, with a trace,
``breakdown``; its last key, ``checks``, holds each number compared beside
its limit, which are also the last lines on standard error. Without a TPU,
or with fewer chips than the cell asks for, the run exits 2 and prints no
result.

``--control 1`` puts the fp8 control in the program's place for the gap
check, which it has to fail (for setting and proving the limit; the
benchmark's own runs do not use it).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import suite  # noqa: E402


def driver(name: str):
    import importlib
    return importlib.import_module(f"drivers.{name}")


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path | None = None,
             bench_dir: Path = HERE, require_tpu: bool = True,
             fault: str = "", control: bool = False, log=print) -> dict:
    """One run of one cell: the result object (without printing)."""
    cell = suite.load_cell(name, root, bench_dir)
    out = driver(cell.traffic["driver"]).run(
        cell, seed, seconds, trace, t_start, require_tpu=require_tpu,
        fault=fault, control=control, log=log)
    device = dict(out["device"])
    breakdown = None
    if trace:
        metrics = suite.read_per_layer(cell, out["rec"])
        t = out["rec"].get("trace")
        if t:
            device["busy_s"] = t["busy_s"]
            device["window_s"] = t["window_s"]
            breakdown = {"device_ops": t["device_ops"],
                         "idle_gaps": t["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    correct = all(c["ok"] for c in out["checks"])
    return {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            "breakdown": breakdown, "checks": out["checks"]}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="run.py", description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from suite import NoChip, RunError
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START,
                       control=bool(args.control))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 2
    except (RunError, FileNotFoundError, KeyError) as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 1
    for c in res["checks"]:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(suite.result_line(
        correct=res["correct"], attempted=res["attempted"],
        failed=res["failed"], metrics=res["metrics"], device=res["device"],
        checks=res["checks"], breakdown=res["breakdown"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
