"""Kernel ``mla_paged_attention_decode`` (``kernels/paged_attention``, the
latent decode walk): the latent bytes and FLOPs each decoded token needs at
its live context (``bench/flops_mla_moe.decode_attn``), at the chip's
roofline, over the summed device time of the kernel's events."""
import sys

from bench import flops, flops_mla_moe, trace_reduce
from bench.records import decoded

KERNEL = r"^mla_paged_attention_decode\b"


def read(rec):
    ops = rec["trace"]["devices"].get(0, [])
    ns, count = trace_reduce.kernel_ns(ops, KERNEL)
    toks = decoded(rec)
    if not count or not toks:
        return None
    m = rec["model"]
    work = [flops_mla_moe.decode_attn(m, c) for _, c in toks]
    f, b = sum(w[0] for w in work), sum(w[1] for w in work)
    t, bound = flops.roofline_s(f, b, rec["peak"])
    print(f"[bench] latent decode attention: {count} kernel events, "
          f"{ns * 1e-6:.3f} ms for {len(toks)} tokens; needs {f:.4g} FLOP, "
          f"{b:.4g} B; {bound}-bound", file=sys.stderr)
    return 100.0 * t / (ns * 1e-9)
