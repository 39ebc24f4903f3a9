"""Kernel ``mla_decode_attention``: the least time the chip needs for the
MLA decode calls in the traced window (``counts_mla_moe.mla_decode_call``
over the valid latent positions of the active slots, the larger of FLOPs
over peak FLOP/s and bytes over peak bandwidth) over the kernel's device
time in the trace, in percent. At ~120 FLOP/byte it lies near half the
v5e's ridge, so both bounds are near."""
import counts_mla_moe as cm


def read(rec):
    t, peak = rec.get("trace"), rec.get("peak")
    if rec.get("kind") != "engine" or not t or not peak:
        return None
    k = t["kernels"].get("mla_decode_attention")
    a, b = rec["traced_steps"]
    steps = rec["steps"][a:b]
    if not k or not k["calls"] or not k["s"] or not steps:
        return None
    m = cm.dims(rec["config"])
    least = 0.0
    for s in steps:
        fl, by = cm.mla_decode_call(m, s["lengths"])
        least += cm.least_time(fl, by, peak)[0]
    # each decode step calls the kernel once per layer
    least *= k["calls"] / len(steps)
    return 100.0 * least / k["s"]
