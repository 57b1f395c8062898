"""The chat cell's TTFT p90, read in the traced run: the end-to-end
definition (first token received minus the time the request was due, over
every request due in the window), kept per layer because its runs spread
too widely for any bound the check allows.  The profiler is stopped off
the clients' loop, so its seconds of stopping delay no submission."""
import math


def read(rec):
    v = rec.get("e2e", {}).get("ttft_p90_ms")
    return v if v is not None and math.isfinite(v) else None
