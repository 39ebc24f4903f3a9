"""Model step, whole: FLOPs the window's work needs (``counts``: each
admitted prompt's prefill and each step's decode over its active slots)
over the window's host-clock seconds times the chip's peak bf16 FLOP/s,
in percent."""
import counts


def read(rec):
    peak = rec.get("peak")
    if rec.get("kind") != "engine" or not peak or not rec["steps"]:
        return None
    m = rec["dims"]
    flops = 0.0
    for s in rec["steps"]:
        flops += sum(counts.prefill_flops(m, n) for n in s["prompts"])
        flops += counts.decode_flops(m, s["lengths"])
    return 100.0 * flops / (rec["window_s"] * peak["bf16_flops_per_s"])
