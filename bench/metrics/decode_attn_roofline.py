"""Kernel ``paged_attention_decode`` (``kernels/paged_attention``): the KV
bytes and FLOPs each decoded token needs at its live context, at the chip's
roofline, over the summed device time of the kernel's events.  Pages past a
row's position and idle rows are not work the traffic needs.  The op takes
its name from the jitted wrapper around the ``pallas_call``."""
import sys

from bench import flops, trace_reduce
from bench.records import decoded

KERNEL = r"^paged_attention_decode\b"


def read(rec):
    ops = rec["trace"]["devices"].get(0, [])
    ns, count = trace_reduce.kernel_ns(ops, KERNEL)
    toks = decoded(rec)
    if not count or not toks:
        return None
    m = rec["model"]
    f = sum(flops.decode_attn(m, c)[0] for _, c in toks)
    b = sum(flops.decode_attn(m, c)[1] for _, c in toks)
    t, bound = flops.roofline_s(f, b, rec["peak"])
    print(f"[bench] decode attention: {count} kernel events, "
          f"{ns * 1e-6:.3f} ms for {len(toks)} tokens; needs {f:.4g} FLOP, "
          f"{b:.4g} B; {bound}-bound", file=sys.stderr)
    return 100.0 * t / (ns * 1e-9)
