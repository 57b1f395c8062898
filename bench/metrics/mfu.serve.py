"""Whole serving step: model FLOPs of every token processed in the traced
window (prompt tokens prefilled, tokens decoded) over the window's length
times the bf16 peak."""
from bench import flops
from bench.records import decoded, prefill_rows


def read(rec):
    m = rec["model"]
    pre = prefill_rows(rec)
    dec = decoded(rec)
    if not pre and not dec:
        return None
    work = sum(flops.model_flops(m, s, n, int(last))
               for _, s, n, last, _, _ in pre)
    work += sum(flops.decode_flops(m, c) for _, c in dec)
    return 100.0 * work / ((rec["t1"] - rec["t0"])
                           * rec["peak"]["bf16_flops"])
