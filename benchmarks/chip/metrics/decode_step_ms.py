"""Serving engine: mean host-clock time of an ``engine.step()`` that
admitted nothing (one batched decode and its sampling), over the window.
A step ends in host reads of the sampled tokens, so the device has
finished."""


def read(rec):
    if rec.get("kind") != "engine":
        return None
    d = [s["dt"] for s in rec["steps"] if not s["admitted"]]
    return 1e3 * sum(d) / len(d) if d else None
