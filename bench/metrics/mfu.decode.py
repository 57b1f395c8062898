"""Model step, decode: model FLOPs of the decoded tokens (each at its live
context, LM head included) over the summed intervals between consecutive
decode-step starts times the bf16 peak: the rate a decoding request sees,
prefill chunks interleaved between its steps included."""
from bench import flops
from bench.records import decoded, steps


def read(rec):
    st = steps(rec, ("decode",))
    if len(st) < 2:
        return None
    t_first, t_last = st[0][1], st[-1][1]
    toks = decoded(rec, t_first, t_last)
    if not toks:
        return None
    m = rec["model"]
    work = sum(flops.decode_flops(m, c) for _, c in toks)
    return 100.0 * work / ((t_last - t_first) * rec["peak"]["bf16_flops"])
