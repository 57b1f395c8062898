"""Decoder-only LM assembly for all assigned families.

Layers are stacked ``[L, ...]`` and applied with ``lax.scan`` (+ selectable remat
policy) so the HLO contains each block once — this keeps 60-layer 236B-parameter
dry-run compiles tractable and is also what a production launcher wants (compile
time scales O(1) in depth).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import shardings
from .attention import (attn_defs, cache_defs, decode_attention_block,
                        full_attention_block, paged_cache_defs)
from .attn_backend import get_backend
from .cache_spec import NULL_PAGE, CacheFamilySpec, CacheSpec
from .layers import (apply_mlp, apply_norm, embed_defs, embed_tokens, lm_logits,
                     mlp_defs, norm_defs, rope_freqs)
from .mla import (latent_norm, mla_cache_defs, mla_decode_block, mla_defs,
                  mla_full_block, mla_paged_cache_defs)
from .moe import moe_apply, moe_defs, moe_serve_apply
from .params import ParamDef, stack_tree
from .rglru import (rglru_block, rglru_cache_defs, rglru_decode_block, rglru_defs)
from .ssm import (ssm_block, ssm_cache_defs, ssm_decode_block, ssm_defs)


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # full


class DecoderLM:
    """Functional model: all state lives in explicit params/cache pytrees.

    ``attn_backend`` selects how the paged serving paths attend (see
    ``models.attn_backend``): the XLA ``reference`` gather+attend or the
    fused ``pallas`` decode kernel.  Training / static paths are unaffected.
    """

    def __init__(self, cfg: ArchConfig, attn_backend: str = "reference"):
        self.cfg = cfg
        self.attn_backend = get_backend(attn_backend)

    # ------------------------------------------------------------ param defs

    def _attn_defs(self):
        return mla_defs(self.cfg) if self.cfg.use_mla else attn_defs(self.cfg)

    def _dense_block_defs(self, d_ff: Optional[int] = None):
        cfg = self.cfg
        return {
            "ln1": norm_defs(cfg, cfg.d_model),
            "attn": self._attn_defs(),
            "ln2": norm_defs(cfg, cfg.d_model),
            "mlp": mlp_defs(cfg, cfg.d_model, d_ff or cfg.d_ff),
        }

    def _moe_block_defs(self):
        cfg = self.cfg
        return {
            "ln1": norm_defs(cfg, cfg.d_model),
            "attn": self._attn_defs(),
            "ln2": norm_defs(cfg, cfg.d_model),
            "moe": moe_defs(cfg),
        }

    def _rec_block_defs(self):
        cfg = self.cfg
        return {
            "ln1": norm_defs(cfg, cfg.d_model),
            "rec": rglru_defs(cfg),
            "ln2": norm_defs(cfg, cfg.d_model),
            "mlp": mlp_defs(cfg, cfg.d_model, cfg.d_ff),
        }

    def _ssm_block_defs(self):
        cfg = self.cfg
        return {"ln1": norm_defs(cfg, cfg.d_model), "ssm": ssm_defs(cfg)}

    def _hybrid_counts(self) -> Tuple[int, int, int]:
        """(n_groups, n_rec_tail, n_attn). Pattern = (rec, rec, attn)."""
        pat = self.cfg.block_pattern
        L = self.cfg.n_layers
        per = len(pat)
        n_groups = L // per
        tail = L - n_groups * per          # leftover layers are 'rec' by pattern order
        return n_groups, tail, n_groups

    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        defs: Dict[str, Any] = {"embed": embed_defs(cfg),
                                "final_norm": norm_defs(cfg, cfg.d_model)}
        if cfg.n_image_tokens:
            defs["vision_proj"] = ParamDef((cfg.frontend_dim, cfg.d_model),
                                           (None, "embed"))
        if cfg.family == "ssm":
            defs["blocks"] = stack_tree(self._ssm_block_defs(), cfg.n_layers)
        elif cfg.family == "hybrid":
            n_groups, tail, n_attn = self._hybrid_counts()
            defs["rec_blocks"] = stack_tree(self._rec_block_defs(), 2 * n_groups)
            defs["attn_blocks"] = stack_tree(self._dense_block_defs(), n_attn)
            if tail:
                defs["tail_blocks"] = stack_tree(self._rec_block_defs(), tail)
        elif cfg.is_moe:
            k = cfg.first_k_dense
            if k:
                defs["dense_blocks"] = stack_tree(
                    self._dense_block_defs(cfg.d_ff_dense or cfg.d_ff), k)
            defs["blocks"] = stack_tree(self._moe_block_defs(), cfg.n_layers - k)
        else:
            defs["blocks"] = stack_tree(self._dense_block_defs(), cfg.n_layers)
        return defs

    # ------------------------------------------------------------- embedding

    def _embed_inputs(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """Returns (x [B,S,D], loss_mask [B,S])."""
        cfg = self.cfg
        x = embed_tokens(params["embed"], batch["tokens"])
        mask = jnp.ones(batch["tokens"].shape, bool)
        if cfg.n_image_tokens:
            img = batch["image_embeds"].astype(x.dtype) @ params["vision_proj"]
            x = jnp.concatenate([img, x], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros(img.shape[:2], bool), mask], axis=1)
        if cfg.family == "hybrid":          # gemma-style embedding scale
            x = x * math.sqrt(cfg.d_model)
        return x, mask

    # ------------------------------------------------------- full-seq forward

    def _freqs(self, head_dim=None):
        cfg = self.cfg
        if cfg.family == "ssm":
            return None
        hd = head_dim or (cfg.rope_head_dim if cfg.use_mla else cfg.head_dim_)
        return rope_freqs(cfg, hd)

    def forward_hidden(self, params, x, mesh=None, collect_cache: bool = False):
        """x: [B,S,D] -> (hidden, aux_loss, cache_or_None)."""
        cfg = self.cfg
        freqs = self._freqs()
        aux0 = jnp.zeros((), jnp.float32)

        def _sp(x):
            if cfg.seq_parallel and mesh is not None:
                return shardings.constrain(x, mesh, ("batch", "seq_sp", None))
            return x

        def dense_body(carry, p, d_ff=None, window=0):
            x, aux = carry
            h = apply_norm(cfg, p["ln1"], x)
            if cfg.use_mla:
                a = mla_full_block(cfg, p["attn"], h, freqs, q_block=cfg.attn_q_block, unroll=cfg.unroll)
            else:
                a = full_attention_block(cfg, p["attn"], h, freqs, window=window, q_block=cfg.attn_q_block, unroll=cfg.unroll)
            x = x + a
            x = _sp(x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x)))
            return (x, aux), None

        def moe_body(carry, p):
            x, aux = carry
            h = apply_norm(cfg, p["ln1"], x)
            if cfg.use_mla:
                a = mla_full_block(cfg, p["attn"], h, freqs, q_block=cfg.attn_q_block, unroll=cfg.unroll)
            else:
                a = full_attention_block(cfg, p["attn"], h, freqs, q_block=cfg.attn_q_block, unroll=cfg.unroll)
            x = x + a
            m, a_loss = moe_apply(cfg, p["moe"], apply_norm(cfg, p["ln2"], x), mesh=mesh)
            return (_sp(x + m), aux + a_loss), None

        def rec_body(carry, p):
            x, aux = carry
            r, _ = rglru_block(cfg, p["rec"], apply_norm(cfg, p["ln1"], x))
            x = x + r
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return (x, aux), None

        def ssm_body(carry, p):
            x, aux = carry
            s, _ = ssm_block(cfg, p["ssm"], apply_norm(cfg, p["ln1"], x))
            return (x + s, aux), None

        rp = cfg.remat
        carry = (x, aux0)
        if cfg.family == "ssm":
            carry, _ = jax.lax.scan(_remat(ssm_body, rp), carry, params["blocks"], unroll=cfg.unroll)
        elif cfg.family == "hybrid":
            n_groups, tail, _ = self._hybrid_counts()
            rec2 = jax.tree.map(
                lambda a: a.reshape((n_groups, 2) + a.shape[1:]), params["rec_blocks"])

            def group_body(carry, ps):
                rec_p, attn_p = ps
                carry, _ = rec_body(carry, jax.tree.map(lambda a: a[0], rec_p))
                carry, _ = rec_body(carry, jax.tree.map(lambda a: a[1], rec_p))
                carry, _ = dense_body(carry, attn_p, window=cfg.attn_window)
                return carry, None

            carry, _ = jax.lax.scan(_remat(group_body, rp), carry,
                                    (rec2, params["attn_blocks"]),
                                    unroll=cfg.unroll)
            if tail:
                carry, _ = jax.lax.scan(_remat(rec_body, rp), carry,
                                        params["tail_blocks"], unroll=cfg.unroll)
        elif cfg.is_moe:
            if cfg.first_k_dense:
                dff = cfg.d_ff_dense or cfg.d_ff
                carry, _ = jax.lax.scan(
                    _remat(partial(dense_body, d_ff=dff), rp), carry,
                    params["dense_blocks"], unroll=cfg.unroll)
            carry, _ = jax.lax.scan(_remat(moe_body, rp), carry, params["blocks"], unroll=cfg.unroll)
        else:
            carry, _ = jax.lax.scan(
                _remat(partial(dense_body, window=cfg.sliding_window), rp),
                carry, params["blocks"], unroll=cfg.unroll)
        x, aux = carry
        return apply_norm(cfg, params["final_norm"], x), aux

    # ------------------------------------------------------------------ loss

    def loss(self, params, batch, mesh=None, chunk: int = 0):
        """Next-token CE, computed in sequence chunks so the [*, V] logits are
        never materialized for the full sequence (vocab can be 256k)."""
        cfg = self.cfg
        x, tok_mask = self._embed_inputs(params, batch)
        if mesh is not None:
            x = shardings.constrain(x, mesh, ("batch", None, None))
        hidden, aux = self.forward_hidden(params, x, mesh)

        tokens = batch["tokens"]
        n_img = cfg.n_image_tokens
        B, S = hidden.shape[0], hidden.shape[1]
        # labels: next token; image positions and final position masked out
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        labels = jnp.pad(labels, ((0, 0), (n_img, 0)))            # align to hidden
        lmask = jnp.roll(tok_mask, -1, axis=1).at[:, -1].set(False)

        chunk = min(chunk or cfg.loss_chunk, S)
        pad = (-S) % chunk
        if pad:
            hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
            lmask = jnp.pad(lmask, ((0, 0), (0, pad)))
        nc = hidden.shape[1] // chunk
        hc = jnp.moveaxis(hidden.reshape(B, nc, chunk, -1), 1, 0)
        lc = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
        mc = jnp.moveaxis(lmask.reshape(B, nc, chunk), 1, 0)
        vocab_mask = (jnp.arange(cfg.vocab_padded) >= cfg.vocab)

        def ce_chunk(carry, inp):
            h, l, m = inp
            logits = lm_logits(cfg, params["embed"], h).astype(jnp.float32)
            logits = jnp.where(vocab_mask, -1e30, logits)
            if mesh is not None:
                logits = shardings.constrain(logits, mesh, ("batch", None, "vocab"))
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
            nll = jnp.where(m, lse - gold, 0.0)
            tot, cnt = carry
            return (tot + jnp.sum(nll), cnt + jnp.sum(m)), None

        (tot, cnt), _ = jax.lax.scan(
            _remat(ce_chunk, cfg.remat),
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, lc, mc),
            unroll=cfg.unroll)
        loss = tot / jnp.maximum(cnt, 1.0)
        if cfg.is_moe:
            loss = loss + cfg.router_aux_coef * aux / max(1, cfg.n_layers)
        return loss, {"nll": tot / jnp.maximum(cnt, 1.0), "aux": aux, "tokens": cnt}

    # ----------------------------------------------------------------- cache

    def cache_defs(self, batch: int, max_len: int):
        cfg = self.cfg
        if cfg.family == "ssm":
            return {"blocks": stack_tree(ssm_cache_defs(cfg, batch), cfg.n_layers),
                    "pos": ParamDef((batch,), ("batch",), jnp.int32, "zeros")}
        if cfg.family == "hybrid":
            n_groups, tail, n_attn = self._hybrid_counts()
            out = {
                "rec_blocks": stack_tree(rglru_cache_defs(cfg, batch), 2 * n_groups),
                "attn_blocks": stack_tree(
                    cache_defs(cfg, batch, max_len, window=cfg.attn_window), n_attn),
                "pos": ParamDef((batch,), ("batch",), jnp.int32, "zeros"),
            }
            if tail:
                out["tail_blocks"] = stack_tree(rglru_cache_defs(cfg, batch), tail)
            return out
        per = (mla_cache_defs(cfg, batch, max_len) if cfg.use_mla
               else cache_defs(cfg, batch, max_len, window=cfg.sliding_window))
        n = cfg.n_layers if not cfg.is_moe else cfg.n_layers  # same geometry all layers
        out = {"blocks": stack_tree(per, n),
               "pos": ParamDef((batch,), ("batch",), jnp.int32, "zeros")}
        return out

    # ---------------------------------------------------------------- decode

    def decode(self, params, cache, tokens, mesh=None):
        """One-token step. tokens: [B] int32. Returns (logits [B,V], new_cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        x = embed_tokens(params["embed"], tokens)
        if cfg.family == "hybrid":
            x = x * math.sqrt(cfg.d_model)
        freqs = self._freqs()

        def dense_step(x, p, c, window=0):
            h = apply_norm(cfg, p["ln1"], x)
            if cfg.use_mla:
                a, c2 = mla_decode_block(cfg, p["attn"], h, c, pos, freqs)
            else:
                a, c2 = decode_attention_block(cfg, p["attn"], h, c, pos, freqs,
                                               window=window)
            x = x + a
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return x, c2

        def moe_step(x, p, c):
            h = apply_norm(cfg, p["ln1"], x)
            if cfg.use_mla:
                a, c2 = mla_decode_block(cfg, p["attn"], h, c, pos, freqs)
            else:
                a, c2 = decode_attention_block(cfg, p["attn"], h, c, pos, freqs)
            x = x + a
            m, _, _ = moe_serve_apply(cfg, p["moe"], apply_norm(cfg, p["ln2"], x),
                                      jnp.ones(x.shape[:1], bool))
            return x + m, c2

        def rec_step(x, p, c):
            r, c2 = rglru_decode_block(cfg, p["rec"], apply_norm(cfg, p["ln1"], x), c)
            x = x + r
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return x, c2

        def ssm_step(x, p, c):
            s, c2 = ssm_decode_block(cfg, p["ssm"], apply_norm(cfg, p["ln1"], x), c)
            return x + s, c2

        new_cache = dict(cache)
        if cfg.family == "ssm":
            def body(x, pc):
                p, c = pc
                return ssm_step(x, p, c)
            x, new_cache["blocks"] = _scan_blocks(body, x, params["blocks"], cache["blocks"], unroll=cfg.unroll)
        elif cfg.family == "hybrid":
            n_groups, tail, _ = self._hybrid_counts()
            rec2 = jax.tree.map(lambda a: a.reshape((n_groups, 2) + a.shape[1:]),
                                params["rec_blocks"])
            crec2 = jax.tree.map(lambda a: a.reshape((n_groups, 2) + a.shape[1:]),
                                 cache["rec_blocks"])

            def gbody(x, pc):
                (rp, ap), (rc, ac) = pc
                x, c0 = rec_step(x, jax.tree.map(lambda a: a[0], rp),
                                 jax.tree.map(lambda a: a[0], rc))
                x, c1 = rec_step(x, jax.tree.map(lambda a: a[1], rp),
                                 jax.tree.map(lambda a: a[1], rc))
                x, ca = dense_step(x, ap, ac, window=cfg.attn_window)
                rc_new = jax.tree.map(lambda a, b: jnp.stack([a, b]), c0, c1)
                return x, (rc_new, ca)

            x, (nrec, nattn) = _scan_blocks(gbody, x, (rec2, params["attn_blocks"]),
                                            (crec2, cache["attn_blocks"]),
                                            unroll=cfg.unroll)
            new_cache["rec_blocks"] = jax.tree.map(
                lambda a: a.reshape((2 * n_groups,) + a.shape[2:]), nrec)
            new_cache["attn_blocks"] = nattn
            if tail:
                def tbody(x, pc):
                    p, c = pc
                    return rec_step(x, p, c)
                x, new_cache["tail_blocks"] = _scan_blocks(
                    tbody, x, params["tail_blocks"], cache["tail_blocks"], unroll=cfg.unroll)
        elif cfg.is_moe:
            if cfg.first_k_dense:
                # dense lead-in layers share the cache stack head
                k = cfg.first_k_dense
                head = jax.tree.map(lambda a: a[:k], cache["blocks"])
                tail_c = jax.tree.map(lambda a: a[k:], cache["blocks"])

                def dbody(x, pc):
                    p, c = pc
                    return dense_step(x, p, c)
                x, nhead = _scan_blocks(dbody, x, params["dense_blocks"], head, unroll=cfg.unroll)

                def mbody(x, pc):
                    p, c = pc
                    return moe_step(x, p, c)
                x, ntail = _scan_blocks(mbody, x, params["blocks"], tail_c, unroll=cfg.unroll)
                new_cache["blocks"] = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b]), nhead, ntail)
            else:
                def mbody(x, pc):
                    p, c = pc
                    return moe_step(x, p, c)
                x, new_cache["blocks"] = _scan_blocks(mbody, x, params["blocks"],
                                                      cache["blocks"], unroll=cfg.unroll)
        else:
            def dbody(x, pc):
                p, c = pc
                return dense_step(x, p, c, window=cfg.sliding_window)
            x, new_cache["blocks"] = _scan_blocks(dbody, x, params["blocks"],
                                                  cache["blocks"], unroll=cfg.unroll)

        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        new_cache["pos"] = pos + 1
        return logits, new_cache

    # --------------------------------------------------------------- prefill

    def prefill(self, params, batch, mesh=None, logits_idx=None):
        """Forward the full prompt; returns (last-token logits, filled cache).

        ``logits_idx`` ([B] int32, optional) selects which hidden position's
        logits to return instead of the last — serving uses this to prefill
        right-padded bucketed prompts (causal masking makes the padding
        invisible to every real position).

        Implemented as forward + per-layer cache extraction.  For attention
        families the K/V are recomputed from the hidden states layer-by-layer
        during the same scan (cache emitted as scan ys)."""
        cfg = self.cfg
        x, _ = self._embed_inputs(params, batch)
        if mesh is not None:
            x = shardings.constrain(x, mesh, ("batch", None, None))
        B, S = x.shape[0], x.shape[1]
        freqs = self._freqs()
        positions = jnp.arange(S)[None, :]

        cache = None
        if cfg.family == "ssm":
            def body(x, p):
                h = apply_norm(cfg, p["ln1"], x)
                z_in = h @ p["ssm"]["wx"]
                cB_in = h @ p["ssm"]["wB"]
                cC_in = h @ p["ssm"]["wC"]
                s, final = ssm_block(cfg, p["ssm"], h)
                w = cfg.conv_width
                c = {"conv_x": z_in[:, S - w + 1:], "conv_B": cB_in[:, S - w + 1:],
                     "conv_C": cC_in[:, S - w + 1:], "state": final}
                return x + s, c
            x, blocks = _scan_blocks_emit(body, x, params["blocks"], unroll=cfg.unroll)
            cache = {"blocks": blocks, "pos": jnp.full((B,), S, jnp.int32)}
        elif cfg.family == "hybrid":
            x, cache = self._prefill_hybrid(params, x, freqs, S)
        else:
            def body(x, p):
                h = apply_norm(cfg, p["ln1"], x)
                if cfg.use_mla:
                    a = mla_full_block(cfg, p["attn"], h, freqs, q_block=cfg.attn_q_block, unroll=cfg.unroll)
                    ckv_full = h @ p["attn"]["wkv_a"]
                    ckv = latent_norm(cfg, p["attn"], ckv_full)
                    from .layers import apply_rope as _ar
                    krope = _ar(ckv_full[..., cfg.kv_lora_rank:][:, :, None, :],
                                positions, freqs)[:, :, 0, :]
                    c = {"ckv": ckv, "krope": krope}
                else:
                    from .attention import qkv as _qkv
                    from .layers import apply_rope as _ar
                    q, k, v = _qkv(cfg, p["attn"], h)
                    k = _ar(k, positions, freqs)
                    a = full_attention_block(cfg, p["attn"], h, freqs,
                                             window=cfg.sliding_window,
                                             q_block=cfg.attn_q_block,
                                             unroll=cfg.unroll)
                    if cfg.sliding_window:
                        # ring-buffer the last W keys at slots (t % W), the
                        # layout decode's windowed cache reads (cache_defs
                        # allocates min(window, max_len) ring entries)
                        W = min(cfg.sliding_window, S)
                        t = jnp.arange(S - W, S)
                        slots = t % W
                        kw = jnp.zeros((k.shape[0], W) + k.shape[2:],
                                       k.dtype).at[:, slots].set(k[:, S - W:])
                        vw = jnp.zeros((v.shape[0], W) + v.shape[2:],
                                       v.dtype).at[:, slots].set(v[:, S - W:])
                        c = {"k": kw, "v": vw}
                    else:
                        c = {"k": k, "v": v}
                x = x + a
                if "moe" in p:
                    h2 = apply_norm(cfg, p["ln2"], x)
                    m, _, _ = moe_serve_apply(
                        cfg, p["moe"], h2.reshape(B * S, -1),
                        jnp.ones((B * S,), bool))
                    x = x + m.reshape(x.shape)
                else:
                    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
                return x, c

            if cfg.is_moe and cfg.first_k_dense:
                x, head = _scan_blocks_emit(
                    lambda x, p: body(x, p), x, params["dense_blocks"],
                    unroll=cfg.unroll)
                x, tail = _scan_blocks_emit(body, x, params["blocks"], unroll=cfg.unroll)
                blocks = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), head, tail)
            else:
                x, blocks = _scan_blocks_emit(body, x, params["blocks"], unroll=cfg.unroll)
            cache = {"blocks": blocks, "pos": jnp.full((B,), S, jnp.int32)}

        x = apply_norm(cfg, params["final_norm"], x)
        last = x[:, -1] if logits_idx is None else x[jnp.arange(B), logits_idx]
        logits = lm_logits(cfg, params["embed"], last)
        return logits, cache

    def cache_spec(self) -> CacheFamilySpec:
        """The decode-cache taxonomy the serving stack schedules against."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return CacheFamilySpec(kinds=(CacheSpec("state_slot"),),
                                   paged=False, state_slots=True,
                                   checkpointable=True)
        if cfg.family == "hybrid":
            # the bounded local-attention ring lives inside the state slot
            return CacheFamilySpec(
                kinds=(CacheSpec("state_slot"),
                       CacheSpec("state_slot", window=cfg.attn_window)),
                paged=False, state_slots=True, checkpointable=True)
        if cfg.use_mla:
            return CacheFamilySpec(kinds=(CacheSpec("paged_mla"),),
                                   paged=True, prefix_cacheable=True)
        if cfg.sliding_window:
            return CacheFamilySpec(
                kinds=(CacheSpec("windowed_kv", window=cfg.sliding_window),),
                paged=True, window=cfg.sliding_window)
        # vlm prompts are image-conditioned: identical token prefixes do not
        # imply identical KV, so the radix cache must not share them
        return CacheFamilySpec(kinds=(CacheSpec("paged_kv"),), paged=True,
                               prefix_cacheable=not cfg.n_image_tokens,
                               prefix_tokens=cfg.n_image_tokens)

    def supports_paged_decode(self) -> Tuple[bool, str]:
        """Capability report: every decoder-LM family pages now.  Returns
        (True, <cache-family description>) — kept as a tuple for callers that
        still branch on the old gate."""
        return True, self.cache_spec().describe()

    def paged_cache_defs(self, num_pages: int, page_size: int,
                         kv_dtype: str = "bf16"):
        """Abstract defs for the layer-stacked paged pool ({} when the whole
        cache is per-request state slots).  ``kv_dtype == "int8"`` adds the
        per-page scale leaves alongside the int8 payloads."""
        cfg = self.cfg
        if not self.cache_spec().paged:
            return {}
        per = (mla_paged_cache_defs(cfg, num_pages, page_size,
                                    kv_dtype=kv_dtype) if cfg.use_mla
               else paged_cache_defs(cfg, num_pages, page_size,
                                     kv_dtype=kv_dtype))
        return stack_tree(per, cfg.n_layers)

    def state_slot_defs(self, n_slots: int, max_len: int, enc_len: int = 0):
        """Abstract defs for the per-request state-slot pool ({} for pure
        paged families).  Slot axis is axis 1 of every (layer-stacked) leaf;
        layout matches ``cache_defs(n_slots, max_len)`` minus ``pos`` so the
        contiguous decode path can be reused verbatim."""
        if self.cfg.family not in ("ssm", "hybrid"):
            return {}
        defs = self.cache_defs(n_slots, max_len)
        defs.pop("pos")
        return defs

    # ----- paged attention dispatch (everything routes via the backend) -----

    def _paged_attn_decode(self, p, h, kv, layer, meta, freqs):
        return self.attn_backend.paged_decode(self.cfg, p["attn"], h, kv,
                                              layer, meta, freqs)

    def _paged_attn_verify(self, p, h, kv, layer, meta, freqs):
        return self.attn_backend.paged_verify(self.cfg, p["attn"], h, kv,
                                              layer, meta, freqs)

    def _paged_attn_prefill(self, p, h, kv, layer, meta, freqs):
        cfg = self.cfg
        return self.attn_backend.paged_prefill(
            cfg, p["attn"], h, kv, layer, meta, freqs,
            q_block=cfg.attn_q_block, unroll=cfg.unroll)

    def _paged_layers(self, params, kv, x, attend, live):
        """The layer stack of a paged serving step.  The layer-stacked pool
        ``kv`` is carried through the layer loop: ``attend(p, h, kv, l)`` is
        the step's paged attention of layer ``l``, which writes its token
        slots into the pool in place and returns (output, pool).  MoE layers
        run the dropless expert-share layer (``moe.moe_serve_apply``) over
        the tokens ``live`` marks ([*x.shape[:-1]] bool).  Returns (x,
        new_kv, stats): ``stats`` [n_moe_layers, 2] int32 holds each MoE
        layer's held pairs and held experts touched (None for a dense
        model)."""
        cfg = self.cfg

        def dense_step(carry, pl_):
            (x, kv), (p, layer) = carry, pl_
            h = apply_norm(cfg, p["ln1"], x)
            a, kv = attend(p, h, kv, layer)
            x = x + a
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return (x, kv), None

        def moe_step(carry, pl_):
            (x, kv), (p, layer) = carry, pl_
            h = apply_norm(cfg, p["ln1"], x)
            a, kv = attend(p, h, kv, layer)
            x = x + a
            h = apply_norm(cfg, p["ln2"], x)
            m, held, touched = moe_serve_apply(
                cfg, p["moe"], h.reshape(-1, h.shape[-1]), live.reshape(-1))
            return (x + m.reshape(x.shape), kv), jnp.stack([held, touched])

        if not cfg.is_moe:
            x, kv, _ = scan_layers(dense_step, x, kv, params["blocks"],
                                   unroll=cfg.unroll)
            return x, kv, None
        # the dense lead-in layers are the pool's first layers
        k = cfg.first_k_dense
        if k:
            x, kv, _ = scan_layers(dense_step, x, kv, params["dense_blocks"],
                                   unroll=cfg.unroll)
        x, kv, stats = scan_layers(moe_step, x, kv, params["blocks"], first=k,
                                   unroll=cfg.unroll)
        return x, kv, stats

    def decode_paged(self, params, kv, state, meta, tokens, mesh=None,
                     moe_stats: bool = False):
        """One-token continuous-batching decode step.

        kv: layer-stacked paged pool ({} for state-slot families); state:
        layer-stacked per-slot recurrent state ({} for paged families),
        slot i == batch row i; meta: flat per-step metadata from
        ``attn_backend.decode_meta`` — per-slot page-table rows, [B] int32
        absolute positions, and the new token's precomputed physical write
        target, derived once by the engine instead of per block; tokens: [B]
        int32.  Returns (logits [B, V], new_kv, new_state), and with
        ``moe_stats`` the MoE layers' ``stats`` (``_paged_layers``).  Idle
        rows ride along masked: their table rows point at the reserved null
        page (they route no expert pair) and their state rows are
        overwritten at the next admission's prefill."""
        cfg = self.cfg
        pos = meta["pos"]
        if cfg.family in ("ssm", "hybrid"):
            cache = dict(state)
            cache["pos"] = pos
            logits, new_cache = self.decode(params, cache, tokens, mesh)
            new_cache.pop("pos")
            return logits, kv, new_cache
        x = embed_tokens(params["embed"], tokens)
        freqs = self._freqs()
        x, new_kv, stats = self._paged_layers(
            params, kv, x,
            lambda p, h, c, l: self._paged_attn_decode(p, h, c, l, meta,
                                                       freqs),
            live=meta["tables"][:, 0] != NULL_PAGE)
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return (logits, new_kv, state) + ((stats,) if moe_stats else ())

    def verify_paged(self, params, kv, state, meta, tokens, mesh=None):
        """Small-q speculative verify step — ``decode_paged`` over
        ``Q = 1 + speculate_tokens`` candidate tokens per slot.

        tokens: [B, Q] int32 — per slot the last emitted token followed by
        its draft, zero-padded to Q; meta: flat metadata from
        ``attn_backend.verify_meta`` (per-row base positions and live query
        counts).  Every per-token op (embed, norms, attention framing, MLP /
        MoE, logits) is the exact per-row computation of the decode step, so
        row ``j`` of the returned logits equals the decode step's logits at
        position ``pos + j`` bit-for-bit — which is what lets the engine
        accept drafted tokens without changing the greedy stream (the MoE
        layer is dropless, so no token's output depends on another's).  Returns
        (logits [B, Q, V], new_kv, state).  Speculation is gated to paged
        decoder-only families (``serving.speculate.speculation_k``), so the
        state-slot route of ``decode_paged`` has no verify twin."""
        cfg = self.cfg
        assert cfg.family not in ("ssm", "hybrid"), \
            "speculative verify requires a paged cache family"
        x = embed_tokens(params["embed"], tokens)              # [B, Q, d]
        freqs = self._freqs()
        Q = tokens.shape[1]
        live = (meta["tables"][:, :1] != NULL_PAGE) \
            & (jnp.arange(Q)[None, :] < meta["n_q"][:, None])
        x, new_kv, _ = self._paged_layers(
            params, kv, x,
            lambda p, h, c, l: self._paged_attn_verify(p, h, c, l, meta,
                                                       freqs),
            live)
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return logits, new_kv, state

    def prefill_paged(self, params, kv, state, meta, tokens, extras=None,
                      mesh=None, continuation: bool = False,
                      moe_stats: bool = False):
        """Chunk prefill at an offset, straight into the paged pool and/or
        the state-slot pool.  ``continuation`` is a no-op for decoder-only
        models (chunks after the first are already pure page work); enc-dec
        overrides it to skip the per-chunk encoder forward.

        kv: layer-stacked paged pool ({} for state-slot families); state:
        layer-stacked per-slot state ({} for paged families); meta: the flat
        per-step metadata pytree from ``attn_backend.prefill_meta`` —
        page-table rows, state-slot / decode-row indices (out-of-range rows
        — batch padding — scatter nothing), per-row chunk offsets ``start``
        (absolute position of ``tokens[:, 0]``), live counts ``n_tail``
        (``tokens`` is right-padded to a bucket), and the precomputed
        physical write target of every chunk position, derived once by the
        engine instead of per layer; tokens: [B, T] int32; extras: optional
        frontend inputs ({"image_embeds": [B, n_img, D]} for vlm).

        With ``start == 0`` this is a full (or first-chunk) prompt prefill;
        with ``start > 0`` the first ``start`` positions are read from pages
        already resident in the pool — radix prefix-cache hits and earlier
        chunks of the same prompt alike.  Padding rows/positions write to
        the null page.  Returns (last-real-token logits [B, V], new_kv,
        new_state), and with ``moe_stats`` the MoE layers' ``stats``
        (``_paged_layers``; padding routes no expert pair)."""
        cfg = self.cfg
        slots, n_tail = meta["slots"], meta["n_tail"]
        if cfg.family in ("ssm", "hybrid"):
            return self._prefill_state_slots(params, kv, state, slots, n_tail,
                                             tokens, mesh)
        x = embed_tokens(params["embed"], tokens)
        n_live = n_tail
        if cfg.n_image_tokens:
            # vlm: the hidden sequence is image tokens ++ text tokens; the
            # image prefix is always live and always at positions [0, n_img)
            img = (extras["image_embeds"].astype(x.dtype)
                   @ params["vision_proj"])
            x = jnp.concatenate([img, x], axis=1)
            n_live = n_tail + cfg.n_image_tokens
        freqs = self._freqs()
        B, T = x.shape[0], x.shape[1]
        x, new_kv, stats = self._paged_layers(
            params, kv, x,
            lambda p, h, c, l: self._paged_attn_prefill(p, h, c, l, meta,
                                                        freqs),
            live=jnp.arange(T)[None, :] < n_live[:, None])
        x = apply_norm(cfg, params["final_norm"], x)
        last = x[jnp.arange(B), n_live - 1]
        logits = lm_logits(cfg, params["embed"], last)
        return (logits, new_kv, state) + ((stats,) if moe_stats else ())

    # ----------------------------------------------- state-slot prefill path

    def _prefill_state_slots(self, params, kv, state, slots, n_tail, tokens,
                             mesh=None):
        """Full-prompt prefill for recurrent families: run the masked full-
        sequence forward (right-padding is a recurrence no-op under
        ``length_mask``), extract each layer's final state + conv taps at the
        *true* prompt length, and scatter them into the state pool at rows
        ``slots`` (out-of-range rows are dropped)."""
        cfg = self.cfg
        B, S = tokens.shape
        x = embed_tokens(params["embed"], tokens)
        if cfg.family == "hybrid":
            x = x * math.sqrt(cfg.d_model)
        mask = jnp.arange(S)[None, :] < n_tail[:, None]              # [B, S]

        def conv_tail(u, width):
            """Last ``width - 1`` rows of ``u`` before each row's true length
            (zeros where the prompt is shorter than the conv receptive
            field, matching the zero-initialized decode conv cache)."""
            idx = n_tail[:, None] - (width - 1) + jnp.arange(width - 1)[None, :]
            valid = idx >= 0
            g = jnp.take_along_axis(u, jnp.maximum(idx, 0)[..., None], axis=1)
            return jnp.where(valid[..., None], g, 0).astype(u.dtype)

        if cfg.family == "ssm":
            def body(x, p):
                h = apply_norm(cfg, p["ln1"], x)
                z_in = h @ p["ssm"]["wx"]
                cB_in = h @ p["ssm"]["wB"]
                cC_in = h @ p["ssm"]["wC"]
                s, final = ssm_block(cfg, p["ssm"], h, length_mask=mask)
                w = cfg.conv_width
                c = {"conv_x": conv_tail(z_in, w), "conv_B": conv_tail(cB_in, w),
                     "conv_C": conv_tail(cC_in, w), "state": final}
                return x + s, c
            x, blocks = _scan_blocks_emit(body, x, params["blocks"],
                                          unroll=cfg.unroll)
            new = {"blocks": blocks}
        else:
            # ring length is whatever the state pool allocated
            x, new = self._hybrid_prefill_body(
                params, x, mask, conv_tail, n_tail,
                L_ring=state["attn_blocks"]["k"].shape[2])
        new_state = jax.tree.map(
            lambda a, nw: a.at[:, slots].set(nw.astype(a.dtype), mode="drop"),
            state, new)
        x = apply_norm(cfg, params["final_norm"], x)
        last = x[jnp.arange(B), n_tail - 1]
        logits = lm_logits(cfg, params["embed"], last)
        return logits, kv, new_state

    def _hybrid_prefill_body(self, params, x, mask, conv_tail, n_tail,
                             L_ring):
        """The one hybrid (RG-LRU + windowed-attention) prefill forward,
        shared by the static path (`_prefill_hybrid`: unmasked, ring length
        ``min(window, S)``) and the state-slot path (`_prefill_state_slots`:
        length-masked, ring length from the state pool).  Emits per-layer
        {conv taps, recurrent state, K/V ring} at each row's true length."""
        cfg = self.cfg
        S = x.shape[1]
        freqs = self._freqs()
        n_groups, tail, _ = self._hybrid_counts()
        positions = jnp.arange(S)[None, :]
        rec2 = jax.tree.map(lambda a: a.reshape((n_groups, 2) + a.shape[1:]),
                            params["rec_blocks"])

        def rec_fwd(x, p):
            h = apply_norm(cfg, p["ln1"], x)
            u_raw = h @ p["rec"]["w_in"]
            r, final = rglru_block(cfg, p["rec"], h, length_mask=mask)
            x = x + r
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            c = {"conv": conv_tail(u_raw, cfg.conv_width), "state": final}
            return x, c

        def gbody(x, ps):
            rp, ap = ps
            x, c0 = rec_fwd(x, jax.tree.map(lambda a: a[0], rp))
            x, c1 = rec_fwd(x, jax.tree.map(lambda a: a[1], rp))
            h = apply_norm(cfg, ap["ln1"], x)
            from .attention import qkv as _qkv
            from .layers import apply_rope as _ar
            q, k, v = _qkv(cfg, ap["attn"], h)
            k = _ar(k, positions, freqs)
            a = full_attention_block(cfg, ap["attn"], h, freqs,
                                     window=cfg.attn_window,
                                     q_block=cfg.attn_q_block,
                                     unroll=cfg.unroll)
            x = x + a
            x = x + apply_mlp(cfg, ap["mlp"], apply_norm(cfg, ap["ln2"], x))
            # ring-buffer the last L_ring *true* keys at slots (t % L_ring),
            # per row: positions past each row's prompt never enter the ring
            b = x.shape[0]
            t = n_tail[:, None] - L_ring + jnp.arange(L_ring)[None, :]  # [B,R]
            ring = t % L_ring
            valid = t >= 0
            rows = jnp.arange(b)[:, None]
            kg = jnp.take_along_axis(
                k, jnp.maximum(t, 0)[..., None, None], axis=1)
            vg = jnp.take_along_axis(
                v, jnp.maximum(t, 0)[..., None, None], axis=1)
            kg = jnp.where(valid[..., None, None], kg, 0)
            vg = jnp.where(valid[..., None, None], vg, 0)
            kw = jnp.zeros((b, L_ring) + k.shape[2:], k.dtype
                           ).at[rows, ring].set(kg.astype(k.dtype))
            vw = jnp.zeros((b, L_ring) + v.shape[2:], v.dtype
                           ).at[rows, ring].set(vg.astype(v.dtype))
            ca = {"k": kw, "v": vw}
            rc = jax.tree.map(lambda a, bb: jnp.stack([a, bb]), c0, c1)
            return x, (rc, ca)

        x, (nrec, nattn) = _scan_blocks_emit(
            gbody, x, (rec2, params["attn_blocks"]), unroll=cfg.unroll)
        new = {
            "rec_blocks": jax.tree.map(
                lambda a: a.reshape((2 * n_groups,) + a.shape[2:]), nrec),
            "attn_blocks": nattn,
        }
        if tail:
            x, ctail = _scan_blocks_emit(rec_fwd, x, params["tail_blocks"],
                                         unroll=cfg.unroll)
            new["tail_blocks"] = ctail
        return x, new

    def _prefill_hybrid(self, params, x, freqs, S):
        """Static-path hybrid prefill: the shared body, unmasked, with every
        row at full length and the ring sized ``min(window, S)``."""
        cfg = self.cfg
        B = x.shape[0]
        n_tail = jnp.full((B,), S, jnp.int32)

        def conv_tail(u, width):
            return u[:, S - width + 1:]

        x, new = self._hybrid_prefill_body(params, x, mask=None,
                                           conv_tail=conv_tail, n_tail=n_tail,
                                           L_ring=min(cfg.attn_window, S))
        return x, {**new, "pos": jnp.full((B,), S, jnp.int32)}


def _scan_blocks(body, x, stacked_params, stacked_cache, unroll=False):
    """scan over (params, cache) pairs, returning (x, new_cache_stacked)."""
    def f(carry, pc):
        x = carry
        x, c = body(x, pc)
        return x, c
    x, cs = jax.lax.scan(f, x, (stacked_params, stacked_cache), unroll=unroll)
    return x, cs


def scan_layers(body, x, kv, stacked_params, first=0, unroll=False):
    """scan a paged step's layers with the layer-stacked pool ``kv`` in the
    carry: ``body((x, kv), (params_i, layer))`` runs stacked layer ``i`` as
    pool layer ``first + i`` and returns ((x, kv), ys).  The pool stays one
    buffer that each layer updates in place.  Returns (x, kv, ys)."""
    n = jax.tree.leaves(stacked_params)[0].shape[0]
    layers = first + jnp.arange(n, dtype=jnp.int32)
    (x, kv), ys = jax.lax.scan(body, (x, kv), (stacked_params, layers),
                               unroll=unroll)
    return x, kv, ys


def _scan_blocks_emit(body, x, stacked_params, unroll=False):
    def f(carry, p):
        x = carry
        x, c = body(x, p)
        return x, c
    x, cs = jax.lax.scan(f, x, stacked_params, unroll=unroll)
    return x, cs
