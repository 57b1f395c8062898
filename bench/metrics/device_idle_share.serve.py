"""Device: share of the traced window in which no operation ran on the
chip (1 - union of the device-op intervals / window), both on the trace's
clock and cut to the window's marks (``trace_reduce.window``)."""


def read(rec):
    s = rec["summary"]
    if s["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
