"""Model step, prefill: model FLOPs of the prompt tokens prefilled (matmuls
at 2 x params, attention at each token's actual context, the LM head only
for a prompt's last chunk, whose logits give the first token) over the
summed ``prefill`` / ``prefill_chunk`` step spans times the bf16 peak."""
from bench import flops
from bench.records import prefill_rows


def read(rec):
    rows = prefill_rows(rec)
    if not rows:
        return None
    m = rec["model"]
    work = sum(flops.model_flops(m, s, n, int(last))
               for _, s, n, last, _, _ in rows)
    spans = {(t0, t1) for *_, t0, t1 in rows}
    busy = sum(t1 - t0 for t0, t1 in spans)
    return 100.0 * work / (busy * rec["peak"]["bf16_flops"])
