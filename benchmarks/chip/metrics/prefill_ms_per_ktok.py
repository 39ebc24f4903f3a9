"""Serving engine, admission and prefill: the time of the steps that
admitted requests, less one mean decode-only step each, per thousand
prompt tokens admitted in the window."""


def read(rec):
    if rec.get("kind") != "engine":
        return None
    dec = [s["dt"] for s in rec["steps"] if not s["admitted"]]
    adm = [s for s in rec["steps"] if s["admitted"]]
    toks = sum(s["prompt_tokens"] for s in adm)
    if not dec or not toks:
        return None
    mean = sum(dec) / len(dec)
    return 1e3 * sum(s["dt"] - mean for s in adm) / (toks / 1e3)
