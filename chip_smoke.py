"""Bring-up smoke test on a TPU: the main serving path at published widths.

  python chip_smoke.py              # one chip: continuous-batching serving
  python chip_smoke.py --chips 4    # four chips: MapReduce data parallelism

One chip (the default): qwen2-0.5b at its published widths (24 layers,
d_model 896, vocab 151,936, random weights from ``--seed``) served through
``Engine.run_offline`` with the fused Pallas kernels (``attn_backend=
"pallas"``), 8 requests of 64-600 prompt tokens and 32 new tokens each,
prefill chunked at 256 tokens.  Checks: every request finishes with its
full budget; the decode step lowered its kernels to Mosaic; and, for 3
requests, the repo's teacher-forced replay (``serving.quant_verify.
replay_logits``) under ``pallas`` and ``reference`` agrees within
``logit_tol`` with no token flip where the reference margin exceeds twice
the observed error.

Four chips: ``mapreduce_value_and_grad`` (the paper's map / combine /
reduce) over a 4-way data mesh on a global batch of 8 x 256 tokens, against
``jax.value_and_grad`` of the same loss on one device, then 3 steps of
``make_train_step(engine="mapreduce")``.

Everything runs in this one process (a chip belongs to one process).  It
exits non-zero, printing no result line, when JAX finds no TPU or a check
fails; the last line of a passing run is one JSON object naming the device.
Times printed here are smoke figures, not benchmarks.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import zlib

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ServeConfig, get_arch  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

ARCH = "qwen2-0.5b"
REPLAYED = (0, 3, 7)          # requests replayed under both backends
# bf16 gradients carry 8 mantissa bits (relative rounding 2^-8 ~ 0.4%).  The
# sharded path rounds four per-shard gradients and sums them in fp32; the
# one-device path rounds one full-batch gradient; accumulation orders
# differ too.  Five roundings' worth bounds the relative L2 error of the
# whole gradient.  It is not a per-leaf bound: a leaf whose exact gradient
# vanishes (a key bias, which softmax cancels) holds rounding noise only.
GRAD_RTOL = 5 * 2.0 ** -8
LOSS_RTOL = 2.0 ** -8


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache loads
    included, which is what makes a warm run short), the number of
    programs compiled or loaded, and cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def fail(msg: str):
    print(f"[smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


def serve_requests(cfg, scfg, seed: int, clock: CompileClock):
    """Serve 8 seeded requests through the engine; returns (engine,
    prompts, results, metrics, wall seconds, compile seconds of the run)."""
    from repro.launch.serve import make_prompts
    from repro.serving import Engine
    rule = argparse.Namespace(seed=seed, requests=8, mixed=True,
                              min_prompt_len=64, prompt_len=600, gen=32,
                              shared_prefix=0)
    prompts, _ = make_prompts(rule, cfg.vocab)
    budgets = [32] * len(prompts)
    eng = Engine(cfg, scfg, seed=seed)
    c0, t0 = clock.seconds, time.perf_counter()
    results, metrics = eng.run_offline(prompts, budgets)
    wall, compile_s = time.perf_counter() - t0, clock.seconds - c0
    for r, b in zip(results, budgets):
        check(not r.failed and not r.error,
              f"request {r.rid} failed: {r.error!r}")
        check(len(r.tokens) == b,
              f"request {r.rid}: {len(r.tokens)} of {b} tokens")
    check(metrics["attn_backend"] == scfg.attn_backend,
          f"served by {metrics['attn_backend']}, not {scfg.attn_backend}")
    check(metrics["chunked_prefill_steps"] > 0,
          "no continuation prefill chunk ran")
    return eng, prompts, results, metrics, wall, compile_s


def decode_step_hlo(eng) -> str:
    """Compiled HLO of the engine's decode step at its serving shapes."""
    meta = eng._decode_plan([])
    state = eng.states.state if eng.states is not None else {}
    tokens = jnp.zeros((eng.scfg.max_slots,), jnp.int32)
    return eng._decode.lower(eng.params, eng.pool.kv, state, meta,
                             tokens).compile().as_text()


def replay_check(cfg, scfg, params, prompts, results):
    """Teacher-forced replay of ``REPLAYED`` requests under both backends;
    returns (max |dlogit|, high-margin tokens, exact matches, tokens)."""
    from repro.serving.quant_verify import logit_tol, replay_logits
    err, pairs = 0.0, []
    for i in REPLAYED:
        gen = results[i].tokens
        lp = replay_logits(cfg, scfg, params, prompts[i], gen,
                           kv_dtype="bf16", attn_backend="pallas")
        lr = replay_logits(cfg, scfg, params, prompts[i], gen,
                           kv_dtype="bf16", attn_backend="reference")
        check(bool(np.all(np.isfinite(lp)) and np.all(np.isfinite(lr))),
              f"request {i}: non-finite replay logits")
        err = max(err, float(np.max(np.abs(lp - lr))))
        pairs.append((gen, lr))
    tol = logit_tol(cfg)
    check(err <= tol, f"max |dlogit| {err} over logit_tol {tol}")
    n_high = n_exact = n_tok = 0
    for gen, lr in pairs:
        top2 = np.sort(lr, axis=-1)[:, -2:]
        high = (top2[:, 1] - top2[:, 0]) > 2.0 * err
        greedy = np.argmax(lr, axis=-1)
        gen = np.asarray(gen)
        flips = int(np.sum(high & (greedy != gen)))
        check(flips == 0, f"{flips} high-margin token mismatches")
        n_high += int(np.sum(high))
        n_exact += int(np.sum(greedy == gen))
        n_tok += len(gen)
    return err, tol, n_high, n_exact, n_tok


def one_chip(seed: int, clock: CompileClock):
    cfg = dataclasses.replace(get_arch(ARCH), remat="none")
    scfg = ServeConfig(page_size=16, max_slots=8, max_len=1024,
                       attn_backend="pallas", prefill_chunk_tokens=256)
    print(f"[smoke] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab "
          f"{cfg.vocab}; pool {scfg.max_slots} slots x {scfg.max_len} "
          f"tokens, page {scfg.page_size}, attn_backend "
          f"{scfg.attn_backend}", flush=True)
    c0 = clock.seconds
    eng, prompts, results, metrics, wall, run_compile = serve_requests(
        cfg, scfg, seed, clock)
    digest = zlib.crc32(np.asarray([r.tokens for r in results],
                                   np.int32).tobytes())
    print(f"[smoke] served {len(results)} requests (prompts "
          f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens), "
          f"{metrics['new_tokens']} tokens generated (crc32 {digest:08x}), "
          f"{metrics['chunked_prefill_steps']} continuation chunks, no "
          f"errors", flush=True)
    print(f"[smoke] smoke figure, not a benchmark: wall {wall:.2f} s for "
          f"the serving run, of which compile {run_compile:.2f} s; compile "
          f"{clock.seconds - c0:.2f} s with engine build (weight init, "
          f"pools)", flush=True)
    hlo = decode_step_hlo(eng)
    n_kern = hlo.count("tpu_custom_call")
    check(n_kern > 0, "decode step HLO holds no tpu_custom_call: the "
          "kernels did not lower to Mosaic")
    print(f"[smoke] decode step HLO: {n_kern} tpu_custom_call sites "
          f"(Mosaic kernels)", flush=True)
    err, tol, n_high, n_exact, n_tok = replay_check(
        cfg, scfg, eng.params, prompts, results)
    print(f"[smoke] replay pallas vs reference, requests {list(REPLAYED)}: "
          f"max |dlogit| {err:.6g} (logit_tol {tol}); {n_high} of {n_tok} "
          f"tokens past the 2x-error margin, 0 flips; engine tokens equal "
          f"the reference argmax at {n_exact} of {n_tok}", flush=True)


def bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def four_chips(seed: int):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.mapreduce import mapreduce_value_and_grad
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model, init_params
    from repro.models.steps import make_train_step
    from repro.optim import OptConfig, init_opt_state
    cfg = dataclasses.replace(get_arch(ARCH), remat="none")
    model = build_model(cfg)
    devices = jax.devices()[:4]
    mesh = make_host_mesh(data=4)
    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("data"))
    rng = np.random.RandomState(seed)
    tokens = rng.randint(1, cfg.vocab, size=(8, 256)).astype(np.int32)
    params = jax.device_put(init_params(cfg, jax.random.PRNGKey(seed)), rep)
    batch = {"tokens": jax.device_put(tokens, shard)}
    held = batch["tokens"].sharding.device_set
    check(len(held) == 4, f"batch spans {len(held)} devices, not 4")
    print(f"[smoke] {cfg.name} MapReduce: mesh {dict(mesh.shape)}, global "
          f"batch {tokens.shape[0]}x{tokens.shape[1]} tokens over "
          f"{len(held)} devices", flush=True)

    def loss(p, b):
        return model.loss(p, b, None)

    mr = jax.jit(mapreduce_value_and_grad(loss, mesh, reduce_mode="allreduce"))
    l_mr, g_mr, _, _ = mr(params, batch, None)
    busy = [bytes_in_use(d) for d in devices]
    check(all(b > 0 for b in busy), f"idle device: bytes_in_use {busy}")
    print(f"[smoke] bytes_in_use per device after the MapReduce step: "
          f"{busy}", flush=True)
    # to the host: the one-device reference needs device 0's memory
    l_mr, g_mr = float(l_mr), jax.device_get(g_mr)

    one = devices[0]
    ref = jax.jit(jax.value_and_grad(loss, has_aux=True))
    (l_1, _), g_1 = ref(jax.device_put(params, one),
                        {"tokens": jax.device_put(tokens, one)})
    l_1, g_1 = float(l_1), jax.device_get(g_1)
    check(abs(l_mr - l_1) <= LOSS_RTOL * abs(l_1),
          f"loss {l_mr} vs one-device {l_1}")
    diff2 = ref2 = 0.0
    worst, worst_leaf = 0.0, ""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_mr),
                            jax.tree.leaves(g_1)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        d2, r2 = float(np.sum((a - b) ** 2)), float(np.sum(b ** 2))
        diff2, ref2 = diff2 + d2, ref2 + r2
        if d2 > worst * max(r2, 1e-30):
            worst, worst_leaf = d2 / max(r2, 1e-30), jax.tree_util.keystr(path)
    rel = (diff2 / ref2) ** 0.5
    check(rel <= GRAD_RTOL, f"gradient relative L2 error {rel} over "
          f"{GRAD_RTOL}")
    print(f"[smoke] MapReduce vs one-device value_and_grad: loss {l_mr:.6f} "
          f"vs {l_1:.6f}; gradient relative L2 error {rel:.3g} (tolerance "
          f"{GRAD_RTOL:.3g}); worst leaf {worst_leaf} at {worst ** 0.5:.3g}",
          flush=True)

    opt_cfg = OptConfig(name="adamw", lr=3e-4)
    # donated: each chip holds one copy of the weights and Adam state
    step = jax.jit(make_train_step(cfg, mesh, opt_cfg, engine="mapreduce"),
                   donate_argnums=(0, 1))
    state = (params, jax.device_put(init_opt_state(params, opt_cfg), rep))
    losses = []
    for i in range(3):
        toks = rng.randint(1, cfg.vocab, size=(8, 256)).astype(np.int32)
        p, o, m = step(*state, {"tokens": jax.device_put(toks, shard)})
        state = (p, o)
        losses.append(float(m["loss"]))
    check(all(np.isfinite(losses)), f"non-finite train loss {losses}")
    print(f"[smoke] 3 MapReduce train steps: losses "
          f"{[round(x, 6) for x in losses]}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} devices only")
    print(f"[smoke] device {dev.device_kind}, count {len(devices)}",
          flush=True)
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} TPU devices, found "
          f"{len(devices)}")
    cache = use_compile_cache()
    clock = CompileClock()
    print(f"[smoke] compilation cache: {cache}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed, clock)
    peak = max(d.memory_stats()["peak_bytes_in_use"]
               for d in devices[:args.chips])
    print(f"[smoke] compile {clock.seconds:.2f} s total, {clock.programs} "
          f"programs ({clock.hits} persistent-cache hits); all phases "
          f"{time.perf_counter() - t0:.2f} s; peak_bytes_in_use {peak}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
