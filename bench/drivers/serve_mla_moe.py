"""Serving driver of the latent-attention MoE family (DeepSeek-V2): the
serving driver ``serve`` with this family's configuration check, weights
and reference.

``run`` is ``serve.run`` with three of its collaborators swapped for the
call: ``arch_config`` (the program's configuration, cut as the
configuration file's ``deployment`` states, checked against the
published keys), the weights (``bench/weights_mla_moe.py``) and the
reference (``bench/reference/mla_moe.py``).  Everything else — set-up,
warm shapes, clients, the window, the trace, the replay — is ``serve``'s.
A program that lacks this family's fields (norm epsilon, yarn, group-limited
routing, the expert share) fails ``arch_config`` before any weight is made.
"""
from __future__ import annotations

import contextlib
import dataclasses
import types

from bench import weights_mla_moe
from bench.drivers import serve
from bench.reference import mla_moe


def arch_config(m: dict, conf: dict):
    """The program's ArchConfig for ``conf["arch"]``, cut to this chip's
    share (``n_routed_experts`` held of the deployment's
    ``routed_experts_per_layer``, ``num_hidden_layers`` deep), checked
    against every published key the program has a field for."""
    from repro.configs import get_arch
    dep = conf["deployment"]
    base = get_arch(conf["arch"])
    for field in ("norm_eps", "rope_scaling", "n_group", "n_experts_held"):
        if not hasattr(base, field):
            raise ValueError(f"the program's ArchConfig has no {field!r}: it "
                             f"cannot run {conf['name']}")
    cfg = dataclasses.replace(
        base, n_layers=m["num_hidden_layers"],
        n_experts_held=m["n_routed_experts"],
        first_expert_held=dep["held_experts_first"], remat="none")
    rs = m["rope_scaling"]
    want = {
        "d_model": m["hidden_size"], "n_layers": m["num_hidden_layers"],
        "n_heads": m["num_attention_heads"], "vocab": m["vocab_size"],
        "act": m["hidden_act"], "qkv_bias": m["attention_bias"],
        "tie_embeddings": m["tie_word_embeddings"],
        "rope_theta": m["rope_theta"], "norm": "rmsnorm",
        "norm_eps": m["rms_norm_eps"], "mlp_gated": True, "use_mla": True,
        "kv_lora_rank": m["kv_lora_rank"], "q_lora_rank": m["q_lora_rank"],
        "nope_head_dim": m["qk_nope_head_dim"],
        "rope_head_dim": m["qk_rope_head_dim"],
        "v_head_dim": m["v_head_dim"],
        "rope_scaling": rs["type"], "rope_factor": rs["factor"],
        "rope_original_max_pos": rs["original_max_position_embeddings"],
        "yarn_beta_fast": rs["beta_fast"], "yarn_beta_slow": rs["beta_slow"],
        "yarn_mscale": rs["mscale"],
        "yarn_mscale_all_dim": rs["mscale_all_dim"],
        "first_k_dense": m["first_k_dense_replace"],
        "d_ff_dense": m["intermediate_size"],
        "d_ff_expert": m["moe_intermediate_size"],
        "n_shared_experts": m["n_shared_experts"],
        "top_k": m["num_experts_per_tok"],
        "n_experts": dep["routed_experts_per_layer"],
        "experts_held": m["n_routed_experts"],
        "n_group": m["n_group"], "topk_group": m["topk_group"],
        "norm_topk_prob": m["norm_topk_prob"],
        "routed_scaling_factor": m["routed_scaling_factor"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config {conf['arch']} differs from the "
                         f"bench configuration (program, bench): {bad}")
    if dep["routed_experts_per_layer"] != \
            m["n_routed_experts"] * dep["expert_parallel_chips"]:
        raise ValueError("the deployment's experts do not split evenly "
                         "over its chips")
    return cfg


@contextlib.contextmanager
def _swapped(module, **attrs):
    old = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


def run(ctx, seed, seconds, trace, clock, control=False, replay=True):
    """One run of a latent-attention MoE serving cell (``serve.run``)."""
    conf = ctx["config"]
    dep = conf["deployment"]
    w = types.SimpleNamespace(
        make=lambda m, s: weights_mla_moe.make(m, dep, s),
        check_layout=lambda m, t: weights_mla_moe.check_layout(m, dep, t))
    ref = types.SimpleNamespace(
        served_gaps=lambda m, params, prompt, served, control=False:
        mla_moe.served_gaps(m, dep, params, prompt, served, control=control))
    with _swapped(serve, arch_config=lambda m, _: arch_config(m, conf),
                  weights=w, dense=ref):
        return serve.run(ctx, seed, seconds, trace, clock, control=control,
                         replay=replay)
