"""Whole serving step of the latent-attention MoE family: model FLOPs of
every token processed in the traced window (prompt tokens prefilled,
tokens decoded, ``bench/flops_mla_moe``: latent attention absorbed at
decode and materialized at prefill, the dense and shared MLPs, and the
held experts' 6·D·F for each pair the engine routed to them) over the
window's length times the bf16 peak."""
from bench import flops_mla_moe as fm
from bench.records import decoded, prefill_rows


def read(rec):
    m = rec["model"]
    pre = prefill_rows(rec)
    dec = decoded(rec)
    counts = fm.moe_counts(rec, ("decode", "prefill", "prefill_chunk"))
    if (not pre and not dec) or counts is None:
        return None
    work = sum(fm.model_flops(m, s, n, int(last))
               for _, s, n, last, _, _ in pre)
    work += sum(fm.decode_flops(m, c) for _, c in dec)
    work += fm.expert_work(m, *counts)[0]
    return 100.0 * work / ((rec["t1"] - rec["t0"])
                           * rec["peak"]["bf16_flops"])
