"""Profiler trace -> device busy time, idle gaps, kernel time, breakdown.

The reduction works on plain event tuples, so it is tested on a small
recorded trace without a chip; ``load_xplane`` turns the profiler's
``.xplane.pb`` into those tuples (it needs JAX, and runs in the process
that held the trace).

    device events: (start_ns, end_ns, name) from the "XLA Ops" line of each
                   ``/device:TPU:<id>`` plane (async copies excluded)
    host spans:    (start_ns, end_ns, name) of the benchmark's own
                   ``bench.*`` annotations on the host plane
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
_DEV_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


def op_name(full: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = full.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load_xplane(log_dir: str, devices: list[int] | None = None) -> dict:
    """Device events per device id and the bench host spans of the newest
    trace under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    dev: dict[int, list] = {}
    host: list = []
    for plane in pd.planes:
        m = _DEV_PLANE.match(plane.name)
        if m:
            did = int(m.group(2))
            if devices is not None and did not in devices:
                continue
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    dev[did] = [(e.start_ns, e.start_ns + e.duration_ns,
                                 op_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    return {"device": dev, "host": host}


def union(intervals: list[tuple]) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` covering the given intervals."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float) -> list[tuple]:
    return [(max(s, t0), min(e, t1), *rest) for s, e, *rest in intervals
            if e > t0 and s < t1]


def busy_ns(events: list[tuple], t0: float, t1: float) -> float:
    return sum(e - s for s, e in union(clip(events, t0, t1)))


def gaps(events: list[tuple], t0: float, t1: float
         ) -> list[tuple[float, float]]:
    """Idle intervals of the device inside ``[t0, t1]``."""
    out, cur = [], t0
    for s, e in union(clip(events, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def self_times(events: list[tuple]) -> dict[str, float]:
    """Per-op time not covered by ops nested inside it (the while loop
    around a scanned layer holds every op of the layer)."""
    out: dict[str, float] = {}
    stack: list[list] = []      # [end, name, child_ns]

    def pop() -> None:
        end, name, start, child = stack.pop()
        out[name] = out.get(name, 0.0) + (end - start) - child
        if stack:
            stack[-1][3] += end - start

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            pop()
        stack.append([e, name, s, 0.0])
    while stack:
        pop()
    return out


def kernel_ns(events: list[tuple], pattern: str, t0: float,
              t1: float) -> tuple[float, int]:
    """Summed device time and count of the ops whose name holds
    ``pattern``, inside the window."""
    sel = [(s, e) for s, e, n in clip(events, t0, t1) if pattern in n]
    return sum(e - s for s, e in sel), len(sel)


def covering(spans: list[tuple], t: float) -> str | None:
    """The innermost (shortest) host span around time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else None


def window(trace: dict) -> tuple[float, float]:
    """The traced window: the ``bench.window`` span, else the extent of
    all device events."""
    spans = [(s, e) for s, e, n in trace["host"] if n == WINDOW_SPAN]
    if spans:
        return spans[0]
    evs = [x for v in trace["device"].values() for x in v]
    if not evs:
        raise ValueError("trace holds no device events")
    return min(s for s, *_ in evs), max(e for _, e, *_ in evs)


def reduce(trace: dict, kernels: dict[str, str] | None = None,
           top: int = 10) -> dict | None:
    """Everything the per-layer readers and the breakdown take from a
    trace: busy and window seconds (busy averaged over devices), per-kernel
    device seconds and counts, the top ops by self time and the idle gaps
    by the host span that covered them. None when the trace holds no
    device plane (a run off the chip)."""
    dev = trace["device"]
    if not dev:
        return None
    t0, t1 = window(trace)
    busy = [busy_ns(ev, t0, t1) for ev in dev.values()]
    selfs: dict[str, float] = {}
    idle: dict[str, float] = {}
    spans = [x for x in trace["host"] if x[2] != WINDOW_SPAN]
    kern = {}
    for name, pattern in (kernels or {}).items():
        ns, n = 0.0, 0
        for ev in dev.values():
            k_ns, k_n = kernel_ns(ev, pattern, t0, t1)
            ns, n = ns + k_ns, n + k_n
        kern[name] = {"s": ns / 1e9, "calls": n}
    for ev in dev.values():
        for name, ns in self_times(clip(ev, t0, t1)).items():
            selfs[name] = selfs.get(name, 0.0) + ns
        for s, e in gaps(ev, t0, t1):
            label = covering(spans, (s + e) / 2) or "no bench span"
            idle[label] = idle.get(label, 0.0) + (e - s)
    n_dev = len(dev)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "devices": n_dev,
        "kernels": kern,
        "device_ops": [[n, v / n_dev / 1e9] for n, v in rank(selfs)],
        "idle_gaps": [[n, v / n_dev / 1e9] for n, v in rank(idle)],
    }
