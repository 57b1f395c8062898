"""Kernel ``ragged_prefill_attend`` (``kernels/ragged_prefill``): the causal
attention the prefilled prompt tokens need (each query over its own
context; K and V read once), at the chip's roofline, over the summed device
time of the kernel's events."""
import sys

from bench import flops, trace_reduce
from bench.records import prefill_rows

KERNEL = r"^ragged_prefill_attend\b"


def read(rec):
    ops = rec["trace"]["devices"].get(0, [])
    ns, count = trace_reduce.kernel_ns(ops, KERNEL)
    rows = prefill_rows(rec)
    if not count or not rows:
        return None
    m = rec["model"]
    f = sum(flops.attn_flops(m, s, n) for _, s, n, *_ in rows)
    b = sum(flops.attn_bytes(m, s, n) for _, s, n, *_ in rows)
    t, bound = flops.roofline_s(f, b, rec["peak"])
    print(f"[bench] prefill attention: {count} kernel events, "
          f"{ns * 1e-6:.3f} ms; needs {f:.4g} FLOP, {b:.4g} B; "
          f"{bound}-bound", file=sys.stderr)
    return 100.0 * t / (ns * 1e-9)
