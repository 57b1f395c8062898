"""Model step: held (token, expert) pairs per held expert touched, over the
decode steps that started in the traced window (the ``moe_held`` and
``moe_touched`` counts on their spans, summed over the MoE layers) — how
near each expert here comes to its load in the deployment."""
from bench import flops_mla_moe


def read(rec):
    counts = flops_mla_moe.moe_counts(rec, ("decode",))
    if not counts or not counts[1]:
        return None
    return counts[0] / counts[1]
