"""Model step, whole, for a latent-attention MoE model: FLOPs the window's
work needs (``counts_mla_moe``: each admitted prompt's non-absorbed
prefill and each step's absorbed decode over its active slots, the held
experts' rows as their expectation) over the window's host-clock seconds
times the chip's peak bf16 FLOP/s, in percent."""
import counts_mla_moe as cm


def read(rec):
    peak = rec.get("peak")
    if rec.get("kind") != "engine" or not peak or not rec["steps"] \
            or "published" not in rec["config"]:
        return None
    m = cm.dims(rec["config"])
    flops = 0.0
    for s in rec["steps"]:
        flops += sum(cm.prefill_flops(m, n) for n in s["prompts"])
        flops += cm.decode_flops(m, s["lengths"])
    return 100.0 * flops / (rec["window_s"] * peak["bf16_flops_per_s"])
