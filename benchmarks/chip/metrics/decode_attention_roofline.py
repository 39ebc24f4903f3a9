"""Kernel ``decode_attention``: the least time the chip needs for the
calls in the traced window (``counts.decode_attention_call`` over the
valid cache positions of the active slots, the larger of FLOPs over peak
FLOP/s and bytes over peak bandwidth) over the kernel's device time in the
trace, in percent. Bytes bound it: it moves about one byte per FLOP."""
import counts


def read(rec):
    t, peak = rec.get("trace"), rec.get("peak")
    if rec.get("kind") != "engine" or not t or not peak:
        return None
    k = t["kernels"].get("decode_attention")
    a, b = rec["traced_steps"]
    steps = rec["steps"][a:b]
    if not k or not k["calls"] or not k["s"] or not steps:
        return None
    m = counts.dims(rec["config"])
    least = 0.0
    for s in steps:
        fl, by = counts.decode_attention_call(m, s["lengths"])
        least += counts.least_time(fl, by, peak)[0]
    # each step calls the kernel once per layer: the trace's calls per step
    least *= k["calls"] / len(steps)
    return 100.0 * least / k["s"]
