"""The benchmark's data, found by name: BENCHMARK.json, the configuration
files, the traffic mixes, the per-layer readers and the table of peaks.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under this directory:

    configs/<config>.json     sizes, source, departures, correctness limit;
                              its "reference" names reference/<module>.py
    reference/<module>.py     the plain reference a configuration names, and
                              the widths the program is checked on (the
                              contract: ``reference/__init__.py``)
    traffic/<traffic>.json    the mix; its "driver" names drivers/<driver>.py
    metrics/<metric>.py       ``read(rec) -> float | None``; a metric named
                              ``base.suffix`` falls back to ``metrics/base.py``
    peaks.json                peaks keyed by JAX ``device_kind``

so a new cell, mix, configuration or metric is new files and new
BENCHMARK.json entries, and no edit here.
"""
from __future__ import annotations

import importlib.util
import json
import math
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent


class RunError(Exception):
    """The run cannot produce a result."""


class NoChip(RunError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def find_root(start: Path = HERE) -> Path:
    """The checkout: the nearest directory above this one holding
    BENCHMARK.json."""
    for d in (start, *start.parents):
        if (d / "BENCHMARK.json").is_file():
            return d
    raise FileNotFoundError("no BENCHMARK.json above " + str(start))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path
    config_file: Path
    bench_dir: Path = HERE


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path | None = None,
              bench_dir: Path = HERE) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    root = root or find_root(bench_dir)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config_file = root / configs[w["config"]]["file"]
    config = json.loads(config_file.read_text())
    config.setdefault("name", w["config"])
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    traffic.setdefault("name", w["traffic"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)],
                root=root, config_file=config_file, bench_dir=bench_dir)


def _load(path: Path, prefix: str):
    mod_name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cell: Cell):
    """The reference module the cell's configuration names by its
    ``reference`` key: ``reference/<module>.py``. A key or a module that is
    not there is a ``RunError``."""
    name = cell.config.get("reference")
    if not isinstance(name, str) or not name:
        raise RunError(f"{cell.config_file} names no reference module (its "
                       f"\"reference\" key)")
    path = cell.bench_dir / "reference" / f"{name}.py"
    if name.startswith("_") or "/" in name or not path.is_file():
        raise RunError(f"{cell.config_file} names reference {name!r}, and "
                       f"there is no {path}")
    return _load(path, "bench_reference_")


def reader(metric: str, bench_dir: Path = HERE):
    """The ``read`` function of a per-layer metric: ``metrics/<name>.py``,
    else ``metrics/<name up to its first dot>.py``."""
    base = bench_dir / "metrics"
    for stem in (metric, metric.split(".", 1)[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            return _load(path, "bench_metric_").read
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r} "
                            f"under {base}")


def read_per_layer(cell: Cell, rec: dict) -> dict:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"], cell.bench_dir)(rec)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peaks(kind: str, bench_dir: Path = HERE) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


# ----------------------------------------------------------------- output --
def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list[dict],
                breakdown: dict | None = None) -> str:
    """The run's last line of standard output. ``checks`` (each number
    compared beside its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = [{**c, "value": c["value"] if math.isfinite(c["value"])
                      else None} for c in checks]
    return json.dumps(out)


def check(name: str, value: float, limit: float) -> dict:
    """One number compared: passes while ``value <= limit``."""
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(value <= limit)}
