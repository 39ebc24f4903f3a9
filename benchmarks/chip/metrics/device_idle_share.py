"""Device: the share of the traced window in which no operation ran on
the chips the cell uses (1 - union of device op intervals / window,
averaged over chips), in percent, from the profiler trace of the process
that holds the chips."""


def read(rec):
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
