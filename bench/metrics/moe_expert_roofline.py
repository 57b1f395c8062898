"""Kernel ``gmm`` (``kernels/grouped_matmul``, the held experts' grouped
matmuls): the work of the pairs routed to held experts in the traced
window — 6·D·F FLOPs a pair, the touched experts' weights and each pair's
activation in and out (``bench/flops_mla_moe.expert_work``), counted from
the engine's step spans — at the chip's roofline, over the summed device
time of the op's events.  The op takes its name from Megablox's jitted
``gmm``."""
import sys

from bench import flops, flops_mla_moe, trace_reduce

KERNEL = r"^gmm\b"


def read(rec):
    ops = rec["trace"]["devices"].get(0, [])
    ns, count = trace_reduce.kernel_ns(ops, KERNEL)
    counts = flops_mla_moe.moe_counts(rec, ("decode", "prefill",
                                            "prefill_chunk"))
    if not count or not counts or not counts[0]:
        return None
    f, b = flops_mla_moe.expert_work(rec["model"], *counts)
    t, bound = flops.roofline_s(f, b, rec["peak"])
    print(f"[bench] held experts: {count} gmm events, {ns * 1e-6:.3f} ms for "
          f"{counts[0]} pairs over {counts[1]} expert visits; needs "
          f"{f:.4g} FLOP, {b:.4g} B; {bound}-bound", file=sys.stderr)
    return 100.0 * t / (ns * 1e-9)
