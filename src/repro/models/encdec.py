"""Encoder-decoder transformer (SeamlessM4T backbone).

The audio frontend is a stub per the assignment: ``input_specs`` provides
precomputed frame embeddings at ``d_model``; the backbone is 24 bidirectional
encoder layers + 24 causal decoder layers with cross-attention.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import shardings
from .attention import (attn_defs, cache_defs, cross_attention_block,
                        decode_attention_block, full_attention_block,
                        paged_cache_defs, qkv)
from .attn_backend import get_backend
from .cache_spec import CacheFamilySpec, CacheSpec
from .layers import (apply_mlp, apply_norm, apply_rope, embed_defs, embed_tokens,
                     lm_logits, mlp_defs, norm_defs, rope_freqs)
from .params import ParamDef, stack_tree
from .transformer import _remat, _scan_blocks, _scan_blocks_emit, scan_layers

ENC_LEN_DECODE = 4096   # encoder length assumed for standalone decode cells


class EncDecLM:
    def __init__(self, cfg: ArchConfig, attn_backend: str = "reference"):
        self.cfg = cfg
        self.attn_backend = get_backend(attn_backend)

    def cache_spec(self) -> CacheFamilySpec:
        """Paged decoder self-attention KV + a pinned per-request cross cache
        (computed once from the encoder output, read-only during decode).
        Prompts are frame-conditioned, so token prefixes are not shareable."""
        return CacheFamilySpec(
            kinds=(CacheSpec("paged_kv"), CacheSpec("cross_kv")),
            paged=True, state_slots=True)

    def supports_paged_decode(self):
        return True, self.cache_spec().describe()

    # ------------------------------------------------------------ param defs

    def _enc_block(self):
        cfg = self.cfg
        return {"ln1": norm_defs(cfg, cfg.d_model), "attn": attn_defs(cfg),
                "ln2": norm_defs(cfg, cfg.d_model),
                "mlp": mlp_defs(cfg, cfg.d_model, cfg.d_ff)}

    def _dec_block(self):
        cfg = self.cfg
        return {"ln1": norm_defs(cfg, cfg.d_model), "self_attn": attn_defs(cfg),
                "ln_x": norm_defs(cfg, cfg.d_model), "cross_attn": attn_defs(cfg),
                "ln2": norm_defs(cfg, cfg.d_model),
                "mlp": mlp_defs(cfg, cfg.d_model, cfg.d_ff)}

    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "embed": embed_defs(cfg),
            "enc_blocks": stack_tree(self._enc_block(), cfg.n_enc_layers),
            "dec_blocks": stack_tree(self._dec_block(), cfg.n_dec_layers),
            "enc_norm": norm_defs(cfg, cfg.d_model),
            "final_norm": norm_defs(cfg, cfg.d_model),
        }

    # --------------------------------------------------------------- encoder

    def encode(self, params, frames, mesh=None):
        cfg = self.cfg
        freqs = rope_freqs(cfg, cfg.head_dim_)
        x = frames.astype(jnp.bfloat16)
        if mesh is not None:
            x = shardings.constrain(x, mesh, ("batch", None, None))

        def body(x, p):
            h = apply_norm(cfg, p["ln1"], x)
            x = x + full_attention_block(cfg, p["attn"], h, freqs, causal=False, q_block=cfg.attn_q_block, unroll=cfg.unroll)
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return x, None

        def f(carry, p):
            return body(carry, p)
        x, _ = jax.lax.scan(_remat(f, cfg.remat), x, params["enc_blocks"], unroll=cfg.unroll)
        return apply_norm(cfg, params["enc_norm"], x)

    # ----------------------------------------------------------- decoder/loss

    def _decoder_hidden(self, params, tokens, enc_out, mesh=None):
        cfg = self.cfg
        freqs = rope_freqs(cfg, cfg.head_dim_)
        x = embed_tokens(params["embed"], tokens)
        if mesh is not None:
            x = shardings.constrain(x, mesh, ("batch", None, None))

        def body(carry, p):
            x = carry
            h = apply_norm(cfg, p["ln1"], x)
            x = x + full_attention_block(cfg, p["self_attn"], h, freqs, causal=True, q_block=cfg.attn_q_block, unroll=cfg.unroll)
            x = x + cross_attention_block(cfg, p["cross_attn"],
                                          apply_norm(cfg, p["ln_x"], x), enc_out,
                                          q_block=cfg.attn_q_block, unroll=cfg.unroll)
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return x, None

        x, _ = jax.lax.scan(_remat(body, cfg.remat), x, params["dec_blocks"], unroll=cfg.unroll)
        return apply_norm(cfg, params["final_norm"], x)

    def loss(self, params, batch, mesh=None, chunk: int = 0):
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"], mesh)
        hidden = self._decoder_hidden(params, batch["tokens"], enc_out, mesh)
        tokens = batch["tokens"]
        B, S = tokens.shape
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        lmask = jnp.ones_like(labels, bool).at[:, -1].set(False)
        vocab_mask = (jnp.arange(cfg.vocab_padded) >= cfg.vocab)
        chunk = min(chunk or cfg.loss_chunk, S)
        nc = S // chunk
        hc = jnp.moveaxis(hidden.reshape(B, nc, chunk, -1), 1, 0)
        lc = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
        mc = jnp.moveaxis(lmask.reshape(B, nc, chunk), 1, 0)

        def ce_chunk(carry, inp):
            h, l, m = inp
            logits = lm_logits(cfg, params["embed"], h).astype(jnp.float32)
            logits = jnp.where(vocab_mask, -1e30, logits)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
            tot, cnt = carry
            return (tot + jnp.sum(jnp.where(m, lse - gold, 0.0)),
                    cnt + jnp.sum(m)), None

        (tot, cnt), _ = jax.lax.scan(
            _remat(ce_chunk, cfg.remat),
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, lc, mc),
            unroll=cfg.unroll)
        loss = tot / jnp.maximum(cnt, 1.0)
        return loss, {"nll": loss, "tokens": cnt}

    # ----------------------------------------------------------------- cache

    def cache_defs(self, batch: int, max_len: int, enc_len: int = ENC_LEN_DECODE):
        cfg = self.cfg
        per = cache_defs(cfg, batch, max_len)
        hd = cfg.head_dim_
        cross = {
            "k": ParamDef((batch, enc_len, cfg.n_kv_heads, hd),
                          ("batch", "seq", "kv_heads", "head_dim"), init="zeros"),
            "v": ParamDef((batch, enc_len, cfg.n_kv_heads, hd),
                          ("batch", "seq", "kv_heads", "head_dim"), init="zeros"),
        }
        return {"self": stack_tree(per, cfg.n_dec_layers),
                "cross": stack_tree(cross, cfg.n_dec_layers),
                "pos": ParamDef((batch,), ("batch",), jnp.int32, "zeros")}

    # ---------------------------------------------------------------- decode

    def decode(self, params, cache, tokens, mesh=None):
        cfg = self.cfg
        pos = cache["pos"]
        freqs = rope_freqs(cfg, cfg.head_dim_)
        x = embed_tokens(params["embed"], tokens)

        def body(x, pc):
            p, (cself, ccross) = pc
            h = apply_norm(cfg, p["ln1"], x)
            a, c2 = decode_attention_block(cfg, p["self_attn"], h, cself, pos, freqs)
            x = x + a
            # cross attention against the cached encoder K/V
            hx = apply_norm(cfg, p["ln_x"], x)
            x = x + self._cross_decode(p, hx, ccross["k"], ccross["v"])
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return x, (c2, ccross)

        x, (nself, ncross) = _scan_blocks(
            body, x, params["dec_blocks"], (cache["self"], cache["cross"]),
            unroll=cfg.unroll)
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return logits, {"self": nself, "cross": ncross, "pos": pos + 1}

    # --------------------------------------------------------------- prefill

    def prefill(self, params, batch, mesh=None, logits_idx=None):
        """Encode frames + run the decoder prompt, emitting self/cross caches."""
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"], mesh)
        freqs = rope_freqs(cfg, cfg.head_dim_)
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.arange(S)[None, :]
        x = embed_tokens(params["embed"], tokens)

        def body(x, p):
            h = apply_norm(cfg, p["ln1"], x)
            q, k, v = qkv(cfg, p["self_attn"], h)
            k = apply_rope(k, positions, freqs)
            x = x + full_attention_block(cfg, p["self_attn"], h, freqs, causal=True, q_block=cfg.attn_q_block, unroll=cfg.unroll)
            hx = apply_norm(cfg, p["ln_x"], x)
            x = x + cross_attention_block(cfg, p["cross_attn"], hx, enc_out, q_block=cfg.attn_q_block, unroll=cfg.unroll)
            ck = jnp.einsum("bsd,dhe->bshe", enc_out, p["cross_attn"]["wk"])
            cv = jnp.einsum("bsd,dhe->bshe", enc_out, p["cross_attn"]["wv"])
            if "bk" in p["cross_attn"]:
                ck, cv = ck + p["cross_attn"]["bk"], cv + p["cross_attn"]["bv"]
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return x, ({"k": k, "v": v}, {"k": ck, "v": cv})

        x, (cself, ccross) = _scan_blocks_emit(body, x, params["dec_blocks"], unroll=cfg.unroll)
        x = apply_norm(cfg, params["final_norm"], x)
        last = x[:, -1] if logits_idx is None else x[jnp.arange(B), logits_idx]
        logits = lm_logits(cfg, params["embed"], last)
        cache = {"self": cself, "cross": ccross,
                 "pos": jnp.full((B,), S, jnp.int32)}
        return logits, cache

    # ------------------------------------------------------- paged serving

    def paged_cache_defs(self, num_pages: int, page_size: int,
                         kv_dtype: str = "bf16"):
        """Decoder *self*-attention KV pages, stacked over decoder layers."""
        per = paged_cache_defs(self.cfg, num_pages, page_size,
                               kv_dtype=kv_dtype)
        return stack_tree(per, self.cfg.n_dec_layers)

    def state_slot_defs(self, n_slots: int, max_len: int, enc_len: int):
        """Per-request pinned cross-attention cache: one K/V block of
        ``enc_len`` encoder positions per decoder layer, slot axis 1."""
        cfg = self.cfg
        hd = cfg.head_dim_
        cross = {
            "k": ParamDef((n_slots, enc_len, cfg.n_kv_heads, hd),
                          ("batch", "seq", "kv_heads", "head_dim"),
                          init="zeros"),
            "v": ParamDef((n_slots, enc_len, cfg.n_kv_heads, hd),
                          ("batch", "seq", "kv_heads", "head_dim"),
                          init="zeros"),
        }
        return {"cross": stack_tree(cross, cfg.n_dec_layers)}

    def _cross_decode(self, p, hx, ck, cv):
        """One-token cross-attention against a pinned cross cache row."""
        cfg = self.cfg
        import math as _m
        q = jnp.einsum("bd,dhe->bhe", hx, p["cross_attn"]["wq"])
        if "bq" in p["cross_attn"]:
            q = q + p["cross_attn"]["bq"]
        K = cfg.n_kv_heads
        G = cfg.n_heads // K
        qg = q.reshape(q.shape[0], K, G, cfg.head_dim_)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, ck,
                       preferred_element_type=jnp.float32)
        s = s / _m.sqrt(cfg.head_dim_)
        att = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
        o = jnp.einsum("bkgs,bskd->bkgd", att, cv)
        o = o.reshape(o.shape[0], cfg.n_heads, cfg.head_dim_)
        return jnp.einsum("bhe,hed->bd", o, p["cross_attn"]["wo"])

    def decode_paged(self, params, kv, state, meta, tokens, mesh=None):
        """One-token continuous-batching decode: paged self-attention (via
        the attention backend, ``meta`` per ``attn_backend.decode_meta``) +
        the slot-pinned cross cache.  Returns (logits, new_kv, state) — the
        cross cache is read-only here (written once at prefill)."""
        cfg = self.cfg
        freqs = rope_freqs(cfg, cfg.head_dim_)
        x = embed_tokens(params["embed"], tokens)

        def body(carry, pl_):
            (x, kv), ((p, ccross), layer) = carry, pl_
            h = apply_norm(cfg, p["ln1"], x)
            a, kv = self.attn_backend.paged_decode(cfg, p["self_attn"], h,
                                                   kv, layer, meta, freqs)
            x = x + a
            hx = apply_norm(cfg, p["ln_x"], x)
            x = x + self._cross_decode(p, hx, ccross["k"], ccross["v"])
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return (x, kv), None

        x, kv, _ = scan_layers(body, x, kv,
                               (params["dec_blocks"], state["cross"]),
                               unroll=cfg.unroll)
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return logits, kv, state

    def prefill_paged(self, params, kv, state, meta, tokens, extras=None,
                      mesh=None, continuation: bool = False):
        """Chunk prefill: encode each request's frames, write the decoder
        prompt chunk's self-attention KV through the page tables (``meta``
        per ``attn_backend.prefill_meta``; ``start > 0`` resumes a chunked
        prompt against its already-resident pages), and pin the cross K/V
        into the state slots at rows ``meta["slots"]`` (out-of-range rows —
        batch padding — scatter nothing).

        ``continuation=True`` (chunks after the first of a long prompt)
        skips the encoder entirely: the cross K/V the first chunk pinned are
        *read back from the state slots* for this chunk's cross-attention —
        the pinned values are the same bf16 the fresh projection would
        produce, so the chunk is bitwise-identical at a fraction of the
        step cost (no per-chunk encoder forward, no re-pin)."""
        cfg = self.cfg
        if continuation:
            return self._prefill_paged_continue(params, kv, state, meta,
                                                tokens, mesh)
        enc_out = self.encode(params, extras["frames"], mesh)
        freqs = rope_freqs(cfg, cfg.head_dim_)
        B = tokens.shape[0]
        slots, n_tail = meta["slots"], meta["n_tail"]
        x = embed_tokens(params["embed"], tokens)

        def body(carry, pl_):
            (x, kv), (p, layer) = carry, pl_
            h = apply_norm(cfg, p["ln1"], x)
            a, kv = self.attn_backend.paged_prefill(
                cfg, p["self_attn"], h, kv, layer, meta, freqs,
                q_block=cfg.attn_q_block, unroll=cfg.unroll)
            x = x + a
            hx = apply_norm(cfg, p["ln_x"], x)
            x = x + cross_attention_block(cfg, p["cross_attn"], hx, enc_out,
                                          q_block=cfg.attn_q_block,
                                          unroll=cfg.unroll)
            ck = jnp.einsum("bsd,dhe->bshe", enc_out, p["cross_attn"]["wk"])
            cv = jnp.einsum("bsd,dhe->bshe", enc_out, p["cross_attn"]["wv"])
            if "bk" in p["cross_attn"]:
                ck, cv = ck + p["cross_attn"]["bk"], cv + p["cross_attn"]["bv"]
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return (x, kv), {"k": ck, "v": cv}

        x, kv, ncross = scan_layers(body, x, kv, params["dec_blocks"],
                                    unroll=cfg.unroll)
        new_state = jax.tree.map(
            lambda a, nw: a.at[:, slots].set(nw.astype(a.dtype), mode="drop"),
            state, {"cross": ncross})
        x = apply_norm(cfg, params["final_norm"], x)
        last = x[jnp.arange(B), n_tail - 1]
        logits = lm_logits(cfg, params["embed"], last)
        return logits, kv, new_state

    def _prefill_paged_continue(self, params, kv, state, meta, tokens,
                                mesh=None):
        """Continuation-chunk prefill: no encoder, no cross re-pin — each
        layer cross-attends the K/V rows the first chunk pinned into the
        state slots (padding rows clamp to row 0 and attend harmless
        garbage; their logits are never read)."""
        cfg = self.cfg
        freqs = rope_freqs(cfg, cfg.head_dim_)
        B = tokens.shape[0]
        slots, n_tail = meta["slots"], meta["n_tail"]
        rows = jnp.clip(slots, 0, state["cross"]["k"].shape[1] - 1)
        ck = state["cross"]["k"][:, rows]        # [L, B, enc_len, K, D]
        cv = state["cross"]["v"][:, rows]
        x = embed_tokens(params["embed"], tokens)

        def body(carry, pl_):
            (x, kv), ((p, ckl, cvl), layer) = carry, pl_
            h = apply_norm(cfg, p["ln1"], x)
            a, kv = self.attn_backend.paged_prefill(
                cfg, p["self_attn"], h, kv, layer, meta, freqs,
                q_block=cfg.attn_q_block, unroll=cfg.unroll)
            x = x + a
            hx = apply_norm(cfg, p["ln_x"], x)
            q = jnp.einsum("bsd,dhe->bshe", hx, p["cross_attn"]["wq"])
            if "bq" in p["cross_attn"]:
                q = q + p["cross_attn"]["bq"]
            from .attention import chunked_attention
            o = chunked_attention(q, ckl, cvl, causal=False,
                                  q_block=cfg.attn_q_block, unroll=cfg.unroll)
            x = x + jnp.einsum("bshe,hed->bsd", o, p["cross_attn"]["wo"])
            x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
            return (x, kv), None

        x, kv, _ = scan_layers(body, x, kv, (params["dec_blocks"], ck, cv),
                               unroll=cfg.unroll)
        x = apply_norm(cfg, params["final_norm"], x)
        last = x[jnp.arange(B), n_tail - 1]
        logits = lm_logits(cfg, params["embed"], last)
        return logits, kv, state
