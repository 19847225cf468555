"""Composable model builder: one entry point for all assigned architectures.

``build_model(cfg, ctx)`` returns a ``Model`` whose methods are the
*per-device* SPMD programs (they run inside ``shard_map``):

  train_loss(params, batch)          -> (loss, metrics)
  prefill(params, batch)             -> (logits_last, cache)
  decode(params, cache, tokens)      -> (next_tokens, logits_max, cache)
  init(key) / abstract()             -> params / (shapes, specs)
  make_cache(batch, cache_len, ...)  -> fresh decode cache

Design notes:
  * input embedding is UNTIED from the LM head: the input table is the
    row-sparse tensor Zen synchronizes (gather-backward => row-sparse grads,
    the paper's regime); the LM head is an ordinary column-parallel linear
    with dense grads.  Tying would densify the embedding grad and erase the
    paper's setting (DESIGN.md §4).
  * layers are stacked and scanned (``lax.scan`` + per-layer remat) to keep
    HLO size and compile time bounded at 62 layers.
  * audio (whisper) / vision (pixtral) frontends are stubs per the
    assignment: batches carry precomputed frame/patch embeddings.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import (ArchConfig, ParamBuilder, ShardCtx,
                                 make_ctx, zero_cols_from)
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------

def _reduce_layer_stats(t, cfg: ArchConfig):
    """Per-layer stats over the layers: held pairs count the step's work
    and add up, as the balance loss does where each layer adds its own
    (``cfg.seq_aux``); the rest are averaged."""
    summed = {"moe/held_pairs"} | ({"moe/aux_loss"} if cfg.seq_aux else set())
    return {k: (jnp.sum if k in summed else jnp.mean)(v)
            for k, v in t.items()}


def _dense(cfg: ArchConfig) -> ArchConfig:
    """The leading dense layers' view of a config (SwiGLU of d_ff)."""
    return dataclasses.replace(cfg, kind="dense")


def _zeros(shape, dtype, abstract: bool):
    return (jax.ShapeDtypeStruct(tuple(shape), dtype) if abstract
            else jnp.zeros(shape, dtype))


def _stack_cache(make_one: Callable[[], Any], n: int, abstract: bool):
    one = make_one()
    if abstract:
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype), one)
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), one)


# ---------------------------------------------------------------------------
# decoder layer (dense / moe / mla kinds share this)
# ---------------------------------------------------------------------------

def _init_decoder_layer(b: ParamBuilder, cfg: ArchConfig, ctx: ShardCtx,
                        *, cross: bool = False):
    d = cfg.d_model
    L.init_rmsnorm(b, "ln1", d)
    if cfg.is_mla:
        A.init_mla(b, "attn", cfg, ctx)
    else:
        A.init_gqa(b, "attn", cfg, ctx)
    if cross:
        L.init_rmsnorm(b, "lnx", d)
        A.init_gqa(b, "xattn", cfg, ctx, cross=True)
    L.init_rmsnorm(b, "ln2", d)
    if cfg.kind == "moe":
        M.init_moe(b, "ffn", cfg, ctx)
    elif cfg.kind == "enc_dec":
        L.init_gelu_mlp(b, "ffn", d, cfg.d_ff, ctx.tp)
    else:
        L.init_swiglu(b, "ffn", d, cfg.d_ff, ctx.tp)


def _ffn(pl, x, cfg: ArchConfig, ctx: ShardCtx):
    if cfg.kind == "moe":
        return M.moe_ffn(pl, "ffn", x, cfg, ctx)
    if cfg.kind == "enc_dec":
        return L.gelu_mlp(pl, "ffn", x, ctx), {}
    return L.swiglu(pl, "ffn", x, ctx), {}


def _decoder_layer_train(pl, x, cfg: ArchConfig, ctx: ShardCtx, *,
                         positions=None, window: int = 0, enc_out=None):
    eps = cfg.norm_eps
    h = L.rmsnorm(pl["ln1"], x, eps)
    if cfg.is_mla:
        h = A.mla_train(pl, "attn", h, cfg, ctx, positions=positions,
                        window=window)
    else:
        h = A.gqa_train(pl, "attn", h, cfg, ctx, positions=positions,
                        window=window)
    x = x + h
    if enc_out is not None:
        x = x + A.gqa_train(pl, "xattn", L.rmsnorm(pl["lnx"], x), cfg, ctx,
                            kv_src=enc_out, use_rope=False)
    y, stats = _ffn(pl, L.rmsnorm(pl["ln2"], x, eps), cfg, ctx)
    return x + y, stats


def _decoder_layer_decode(pl, x, cache, t, cfg: ArchConfig, ctx: ShardCtx, *,
                          window: int = 0, cross_cache=None):
    eps = cfg.norm_eps
    h = L.rmsnorm(pl["ln1"], x[:, None], eps)[:, 0]
    if cfg.is_mla:
        h, c2 = A.mla_decode(pl, "attn", h, cache, t, cfg, ctx, window=window)
    else:
        h, c2 = A.gqa_decode(pl, "attn", h, cache, t, cfg, ctx, window=window)
    x = x + h
    if cross_cache is not None:
        x = x + A.gqa_cross_decode(
            pl, "xattn", L.rmsnorm(pl["lnx"], x[:, None])[:, 0],
            cross_cache, cfg, ctx)
    y, _ = _ffn(pl, L.rmsnorm(pl["ln2"], x[:, None], eps), cfg, ctx)
    return x + y[:, 0], c2


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    ctx: ShardCtx
    sparse_paths: tuple = ("embed/table",)

    # ---- params ------------------------------------------------------------

    def _build(self, b: ParamBuilder):
        cfg, ctx = self.cfg, self.ctx
        vp = cfg.vocab_padded
        L.init_embedding(b, "embed", cfg.vocab, vp, cfg.d_model)
        L.init_linear(b, "lm_head", cfg.d_model, vp, mode="col", tp=ctx.tp)
        # padded LM-head columns: zero-init (masked out of the logsumexp /
        # argmax anyway, so they receive zero gradient and stay zero)
        zero_cols_from(b, "lm_head_w", cfg.vocab)
        L.init_rmsnorm(b, "ln_f", cfg.d_model)
        if cfg.kind == "ssm":
            b.stacked("layers", cfg.n_layers, functools.partial(
                self._init_ssm_layer))
        elif cfg.kind == "hybrid":
            self._build_hybrid(b)
        elif cfg.kind == "enc_dec":
            self._build_enc_dec(b)
        else:
            for key, lcfg, n in self.stacks():
                b.stacked(key, n, functools.partial(
                    _init_decoder_layer, cfg=lcfg, ctx=ctx))
        if cfg.kind == "vlm":
            L.init_linear(b, "vis_proj", cfg.d_model, cfg.d_model,
                          mode="rep", tp=ctx.tp)

    def stacks(self) -> list:
        """(param key, layer config, depth) of each scanned decoder stack,
        in order: the leading dense layers, if any, then ``layers``."""
        cfg, k = self.cfg, self.cfg.first_k_dense
        if not k:
            return [("layers", cfg, cfg.n_layers)]
        return [("dense_layers", _dense(cfg), k),
                ("layers", cfg, cfg.n_layers - k)]

    def _init_ssm_layer(self, b: ParamBuilder):
        L.init_rmsnorm(b, "ln1", self.cfg.d_model)
        S.init_mamba2(b, "mixer", self.cfg, self.ctx)

    def _build_hybrid(self, b: ParamBuilder):
        cfg, ctx = self.cfg, self.ctx
        every = cfg.shared_attn_every
        self.n_groups = cfg.n_layers // every
        self.n_tail = cfg.n_layers - self.n_groups * every

        def group(bg: ParamBuilder):
            bg.stacked("inner", every, self._init_ssm_layer)

        b.stacked("groups", self.n_groups, group)
        if self.n_tail:
            b.stacked("tail", self.n_tail, self._init_ssm_layer)
        shared = b.child("shared")
        _init_decoder_layer(shared, dataclasses.replace(cfg, kind="dense"),
                            ctx)

    def _build_enc_dec(self, b: ParamBuilder):
        cfg, ctx = self.cfg, self.ctx

        def enc_layer(be: ParamBuilder):
            L.init_rmsnorm(be, "ln1", cfg.d_model)
            A.init_gqa(be, "attn", cfg, ctx)
            L.init_rmsnorm(be, "ln2", cfg.d_model)
            L.init_gelu_mlp(be, "ffn", cfg.d_model, cfg.d_ff, ctx.tp)

        b.stacked("enc_layers", cfg.n_enc_layers, enc_layer)
        L.init_rmsnorm(b, "ln_enc", cfg.d_model)
        b.stacked("layers", cfg.n_layers, functools.partial(
            _init_decoder_layer, cfg=cfg, ctx=ctx, cross=True))

    def init(self, key) -> tuple[Any, Any]:
        b = ParamBuilder(key, self.cfg.dtype)
        self._build(b)
        return b.params, b.specs

    def abstract(self) -> tuple[Any, Any]:
        b = ParamBuilder(None, self.cfg.dtype, abstract=True)
        self._build(b)
        return b.params, b.specs

    # ---- forward (shared trunk) ---------------------------------------------

    def _embed(self, params, tokens):
        return L.embed_lookup(params, "embed", tokens, self.ctx)

    def _head_logits(self, params, x):
        """Local LM-head logits with padded vocab columns masked to NEG —
        padding can never win an argmax or leak into a softmax."""
        logits_l = L.linear_col(params, "lm_head", x)
        return L.mask_padded_logits(logits_l, self.ctx, self.cfg.vocab)

    def _trunk(self, params, x, *, positions=None, window: int = 0,
               enc_out=None):
        """Run the layer stack on [B, S, d]; returns (x, stats)."""
        cfg, ctx = self.cfg, self.ctx
        if cfg.kind == "ssm":
            def body(carry, pl):
                y = carry + S.mamba2_train(
                    pl, "mixer", L.rmsnorm(pl["ln1"], carry), cfg, ctx)
                return y, {}
            x, _ = lax.scan(jax.checkpoint(body), x, params["layers"])
            return x, {}
        if cfg.kind == "hybrid":
            return self._trunk_hybrid(params, x), {}
        # dense / moe / mla / enc-dec decoder / vlm
        stats = {}
        for key, lcfg, _ in self.stacks():
            def body_stats(carry, pl, lcfg=lcfg):
                return _decoder_layer_train(
                    pl, carry, lcfg, ctx, positions=positions, window=window,
                    enc_out=enc_out)

            x, stats = lax.scan(jax.checkpoint(body_stats), x, params[key])
        return x, _reduce_layer_stats(stats, cfg) if stats else {}

    def _trunk_hybrid(self, params, x):
        cfg, ctx = self.cfg, self.ctx
        dense_cfg = dataclasses.replace(cfg, kind="dense")
        shared = params["shared"]

        def ssm_body(carry, pl):
            return carry + S.mamba2_train(
                pl, "mixer", L.rmsnorm(pl["ln1"], carry), cfg, ctx), None

        def group_body(carry, pg):
            y, _ = _decoder_layer_train(shared, carry, dense_cfg, ctx)
            y, _ = lax.scan(jax.checkpoint(ssm_body), y, pg["inner"])
            return y, None

        x, _ = lax.scan(group_body, x, params["groups"])
        if self.n_tail:
            x, _ = lax.scan(jax.checkpoint(ssm_body), x, params["tail"])
        return x

    def _encode(self, params, frames):
        """Whisper encoder over stub frame embeddings [B, T, d]."""
        cfg, ctx = self.cfg, self.ctx
        Tt = frames.shape[1]
        pos = jnp.arange(Tt)
        half = cfg.d_model // 2
        freqs = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None].astype(jnp.float32) * freqs[None]
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
        x = frames + pe[None].astype(frames.dtype)

        def body(carry, pl):
            h = A.gqa_train(pl, "attn", L.rmsnorm(pl["ln1"], carry), cfg, ctx,
                            causal=False, use_rope=False)
            y = carry + h
            y = y + L.gelu_mlp(pl, "ffn", L.rmsnorm(pl["ln2"], y), ctx)
            return y, None

        x, _ = lax.scan(jax.checkpoint(body), x, params["enc_layers"])
        return L.rmsnorm(params["ln_enc"], x)

    # ---- training ------------------------------------------------------------

    def train_loss(self, params, batch) -> tuple[jnp.ndarray, dict]:
        """batch: tokens [B,S], labels [B,S] (-1 = masked); enc_dec adds
        frames [B,T,d]; vlm adds patches [B,P,d]."""
        cfg, ctx = self.cfg, self.ctx
        tokens, labels = batch["tokens"], batch["labels"]
        x = self._embed(params, tokens)
        positions = None
        enc_out = None
        if cfg.kind == "vlm":
            pat = L.linear_rep(params, "vis_proj", batch["patches"])
            x = jnp.concatenate([pat.astype(x.dtype), x], axis=1)
            positions = jnp.arange(x.shape[1])
            labels = jnp.concatenate(
                [jnp.full(pat.shape[:2], -1, labels.dtype), labels], axis=1)
        if cfg.kind == "enc_dec":
            enc_out = self._encode(params, batch["frames"])
        x, stats = self._trunk(params, x, positions=positions,
                               enc_out=enc_out)
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        if cfg.kind == "vlm":          # drop patch positions before the head
            x = x[:, batch["patches"].shape[1]:]
            labels = labels[:, batch["patches"].shape[1]:]
        loss = L.lm_head_loss_chunked(params, "lm_head", x, labels, ctx,
                                      mask=labels >= 0, valid_vocab=cfg.vocab)
        metrics = {"loss": loss, **{k: jnp.asarray(v) for k, v in
                                    (stats or {}).items()}}
        if cfg.kind == "moe" and "moe/aux_loss" in metrics:
            loss = loss + cfg.aux_alpha * metrics["moe/aux_loss"]
        return loss, metrics

    # ---- serving ---------------------------------------------------------------

    def make_cache(self, batch_local: int, cache_len: int, *,
                   abstract: bool = False):
        cfg, ctx = self.cfg, self.ctx
        def mk_attn_concrete():
            return A.gqa_make_cache(cfg, ctx, batch_local, cache_len,
                                    dtype=cfg.dtype)

        if abstract:
            def mk_attn():
                return jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    jax.eval_shape(mk_attn_concrete))
        else:
            mk_attn = mk_attn_concrete
        cache: dict[str, Any] = {"t": _zeros((), jnp.int32, abstract)}
        if cfg.kind == "ssm":
            cache["layers"] = _stack_cache(
                lambda: S.mamba2_make_cache(cfg, ctx, batch_local,
                                            dtype=cfg.dtype),
                cfg.n_layers, abstract)
        elif cfg.kind == "hybrid":
            every = cfg.shared_attn_every
            ng = cfg.n_layers // every
            nt = cfg.n_layers - ng * every
            cache["ssm"] = _stack_cache(
                lambda: _stack_cache(
                    lambda: S.mamba2_make_cache(cfg, ctx, batch_local,
                                                dtype=cfg.dtype),
                    every, abstract),
                ng, abstract)
            if nt:
                cache["ssm_tail"] = _stack_cache(
                    lambda: S.mamba2_make_cache(cfg, ctx, batch_local,
                                                dtype=cfg.dtype),
                    nt, abstract)
            cache["attn"] = _stack_cache(
                lambda: A.gqa_make_cache(cfg, ctx, batch_local, cache_len,
                                         dtype=cfg.dtype),
                ng, abstract)
        elif cfg.is_mla:
            for key, _, n in self.stacks():
                cache[key] = _stack_cache(
                    lambda: A.mla_make_cache(cfg, ctx, batch_local, cache_len,
                                             dtype=cfg.dtype),
                    n, abstract)
        else:
            cache["layers"] = _stack_cache(mk_attn, cfg.n_layers, abstract)
        if cfg.kind == "enc_dec":
            kv, hd = cfg.n_kv, cfg.hd
            cache["cross"] = _zeros(
                (cfg.n_layers, 2, batch_local, cfg.enc_len, kv, hd),
                cfg.dtype, abstract)
        return cache

    def prime_cross_cache(self, params, frames):
        """Whisper: encode frames and precompute per-layer cross K/V
        ([L, 2, B, enc_len, kv, hd])."""
        enc = self._encode(params, frames)

        def one(_, pl):
            cc = A.gqa_make_cross_cache(pl, "xattn", enc, self.cfg, self.ctx)
            return None, jnp.stack([cc["k"], cc["v"]])

        _, cross = lax.scan(one, None, params["layers"])
        return cross

    def decode(self, params, cache, tokens, *, window: int = 0):
        """One decode step. tokens [B, 1] -> (next [B,1], logit_max, cache)."""
        cfg, ctx = self.cfg, self.ctx
        t = cache["t"]
        x = self._embed(params, tokens)[:, 0]

        if cfg.kind == "ssm":
            def body(carry, inp):
                pl, cl = inp
                y = L.rmsnorm(pl["ln1"], carry[:, None])[:, 0]
                h, c2 = S.mamba2_decode(pl, "mixer", y, cl, cfg, ctx)
                return carry + h, c2
            x, new_layers = lax.scan(body, x, (params["layers"],
                                               cache["layers"]))
            new_cache = {"t": t + 1, "layers": new_layers}
        elif cfg.kind == "hybrid":
            x, new_cache = self._decode_hybrid(params, cache, x, t,
                                               window=window)
        else:
            cross = cache.get("cross")
            new_cache = dict(cache, t=t + 1)
            for key, lcfg, _ in self.stacks():
                def body(carry, inp, lcfg=lcfg):
                    if cross is None:
                        pl, cl = inp
                        cc = None
                    else:
                        pl, cl, cx = inp
                        cc = {"k": cx[0], "v": cx[1]}
                    return _decoder_layer_decode(pl, carry, cl, t, lcfg, ctx,
                                                 window=window, cross_cache=cc)

                xs = ((params[key], cache[key]) if cross is None
                      else (params[key], cache[key], cross))
                x, new_cache[key] = lax.scan(body, x, xs)

        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits_l = self._head_logits(params, x)            # [B, V/tp]
        # greedy global argmax over the vocab-sharded logits (padded
        # columns are already masked to NEG and cannot be selected)
        lf = logits_l.astype(jnp.float32)
        m_l = jnp.max(lf, axis=-1)
        i_l = jnp.argmax(lf, axis=-1).astype(jnp.int32)
        m = ctx.pmax_tp(m_l)
        off = ctx.tp_rank() * lf.shape[-1] if ctx.tp > 1 else 0
        cand = jnp.where(m_l >= m, i_l + off, 0)
        nxt = ctx.pmax_tp(cand)[:, None]
        return nxt, m, new_cache

    def _decode_hybrid(self, params, cache, x, t, *, window: int = 0):
        cfg, ctx = self.cfg, self.ctx
        dense_cfg = dataclasses.replace(cfg, kind="dense")
        shared = params["shared"]

        def ssm_body(carry, inp):
            pl, cl = inp
            y = L.rmsnorm(pl["ln1"], carry[:, None])[:, 0]
            h, c2 = S.mamba2_decode(pl, "mixer", y, cl, cfg, ctx)
            return carry + h, c2

        def group_body(carry, inp):
            pg, ssm_c, attn_c = inp
            y, ac2 = _decoder_layer_decode(shared, carry, attn_c, t,
                                           dense_cfg, ctx, window=window)
            y, sc2 = lax.scan(ssm_body, y, (pg["inner"], ssm_c))
            return y, (sc2, ac2)

        x, (new_ssm, new_attn) = lax.scan(
            group_body, x, (params["groups"], cache["ssm"], cache["attn"]))
        new_cache = dict(cache, t=t + 1, ssm=new_ssm, attn=new_attn)
        if self.cfg.n_layers % self.cfg.shared_attn_every:
            x, new_tail = lax.scan(ssm_body, x,
                                   (params["tail"], cache["ssm_tail"]))
            new_cache["ssm_tail"] = new_tail
        return x, new_cache

    def _prefill_hybrid(self, params, x, Sfull):
        cfg, ctx = self.cfg, self.ctx
        dense_cfg = dataclasses.replace(cfg, kind="dense")
        shared = params["shared"]

        def ssm_body(carry, pl):
            h = L.rmsnorm(pl["ln1"], carry)
            y, cl = S.mamba2_train(pl, "mixer", h, cfg, ctx,
                                   return_cache=True)
            return carry + y, cl

        def group_body(carry, pg):
            h = carry
            kv = A.gqa_prefill_cache(
                shared, "attn", L.rmsnorm(shared["ln1"], h), cfg, ctx)
            y, _ = _decoder_layer_train(shared, h, dense_cfg, ctx)
            y, sc = lax.scan(ssm_body, y, pg["inner"])
            return y, (sc, kv)

        x, (ssm_c, attn_c) = lax.scan(group_body, x, params["groups"])
        cache = {"t": jnp.asarray(Sfull, jnp.int32), "ssm": ssm_c,
                 "attn": attn_c}
        if self.n_tail:
            x, tail_c = lax.scan(ssm_body, x, params["tail"])
            cache["ssm_tail"] = tail_c
        x_last = L.rmsnorm(params["ln_f"], x[:, -1])
        logits_l = self._head_logits(params, x_last)
        return logits_l, cache

    # ---- prefill -----------------------------------------------------------------

    def prefill(self, params, batch):
        """Forward the whole prompt, return (last-token logits_l, cache).

        The produced KV cache is sequence-sharded over model (round-robin),
        matching the decode layout.
        """
        cfg, ctx = self.cfg, self.ctx
        tokens = batch["tokens"]
        B, Ss = tokens.shape
        x = self._embed(params, tokens)
        positions = None
        if cfg.kind == "vlm":
            pat = L.linear_rep(params, "vis_proj", batch["patches"])
            x = jnp.concatenate([pat.astype(x.dtype), x], axis=1)
            positions = jnp.arange(x.shape[1])
        Sfull = x.shape[1]

        if cfg.kind == "ssm":
            def body(carry, pl):
                h = L.rmsnorm(pl["ln1"], carry)
                y, cl = S.mamba2_train(pl, "mixer", h, cfg, ctx,
                                       return_cache=True)
                return carry + y, cl
            x, layer_caches = lax.scan(body, x, params["layers"])
            x_last = L.rmsnorm(params["ln_f"], x[:, -1])
            logits_l = self._head_logits(params, x_last)
            return logits_l, {"t": jnp.asarray(Sfull, jnp.int32),
                              "layers": layer_caches}

        if cfg.kind == "hybrid":
            return self._prefill_hybrid(params, x, Sfull)

        if cfg.kind == "enc_dec":
            enc_out = self._encode(params, batch["frames"])

            def body_ed(carry, pl):
                h = carry
                kv = A.gqa_prefill_cache(
                    pl, "attn", L.rmsnorm(pl["ln1"], h), cfg, ctx)
                cc = A.gqa_make_cross_cache(
                    pl, "xattn", enc_out, cfg, ctx)
                y, _ = _decoder_layer_train(pl, h, cfg, ctx, enc_out=enc_out)
                return y, (kv, jnp.stack([cc["k"], cc["v"]]))
            x, (layer_caches, cross) = lax.scan(body_ed, x, params["layers"])
            x_last = L.rmsnorm(params["ln_f"], x[:, -1])
            logits_l = self._head_logits(params, x_last)
            return logits_l, {"t": jnp.asarray(Sfull, jnp.int32),
                              "layers": layer_caches, "cross": cross}

        # attention archs: run trunk while collecting per-layer K/V shards
        def body(carry, pl):
            h = carry
            y, _ = _decoder_layer_train(pl, h, cfg, ctx, positions=positions)
            kv = A.gqa_prefill_cache(
                pl, "attn", L.rmsnorm(pl["ln1"], h), cfg, ctx)
            return y, kv

        def body_mla(carry, pl, lcfg):
            # latent cache prefill for MLA
            h = carry
            y, _ = _decoder_layer_train(pl, h, lcfg, ctx)
            xin = L.rmsnorm(pl["ln1"], h, cfg.norm_eps)
            kv_c = L.linear_rep(pl["attn"], "kv_down", xin)
            c = L.rmsnorm(pl["attn"]["kv_norm"],
                          kv_c[..., :cfg.mla_kv_rank], cfg.norm_eps)
            kr = L.rope(kv_c[:, :, None, cfg.mla_kv_rank:],
                        jnp.arange(h.shape[1]), cfg.rope_theta,
                        yarn=cfg.yarn)[:, :, 0]
            tp = ctx.tp if ctx.decode_seq_shard else 1
            r = ctx.tp_rank() if (ctx.tp > 1 and ctx.decode_seq_shard) else 0
            sl = -(-Sfull // tp)
            slots = jnp.arange(sl) * tp + r
            safe = jnp.clip(slots, 0, Sfull - 1)
            ok = (slots < Sfull)
            return y, {
                "c": jnp.where(ok[None, :, None], c[:, safe], 0),
                "kr": jnp.where(ok[None, :, None], kr[:, safe], 0),
                "pos": jnp.where(ok, slots, -1).astype(jnp.int32),
            }

        cache = {"t": jnp.asarray(Sfull, jnp.int32)}
        if cfg.is_mla:
            for key, lcfg, _ in self.stacks():
                x, cache[key] = lax.scan(
                    functools.partial(body_mla, lcfg=lcfg), x, params[key])
        else:
            x, cache["layers"] = lax.scan(body, x, params["layers"])
        x_last = L.rmsnorm(params["ln_f"], x[:, -1], cfg.norm_eps)
        logits_l = self._head_logits(params, x_last)
        return logits_l, cache


def build_model(cfg: ArchConfig, ctx: ShardCtx) -> Model:
    m = Model(cfg=cfg, ctx=ctx)
    if cfg.kind == "hybrid":
        every = cfg.shared_attn_every
        m.n_groups = cfg.n_layers // every
        m.n_tail = cfg.n_layers - m.n_groups * every
    return m


def assert_mesh_invariant_params(cfg: ArchConfig, ctx: ShardCtx,
                                 shapes=None) -> None:
    """Enforce the DESIGN.md §9 contract: the *global* parameter pytree
    (paths, shapes, dtypes) must be identical to the tp=1 reference build.

    Runs on every ``build_program`` (abstract builds only — no allocation),
    so a layer init that silently makes a global shape depend on the mesh
    fails loudly at build time instead of surfacing as a cross-mesh loss
    mismatch three experiments later.  The opt-in ``h_pad`` layout is the
    one documented exception (it changes global shapes by design).
    """
    if ctx.h_pad:
        return
    if shapes is None:
        shapes = build_model(cfg, ctx).abstract()[0]
    ref_ctx = make_ctx(cfg, 1, 1)
    ref_shapes = build_model(cfg, ref_ctx).abstract()[0]
    got = jax.tree_util.tree_flatten_with_path(shapes)[0]
    ref = jax.tree_util.tree_flatten_with_path(ref_shapes)[0]
    bad = []
    for (kp, s), (rkp, rs) in zip(got, ref):
        if kp != rkp or s.shape != rs.shape or s.dtype != rs.dtype:
            bad.append(f"{jax.tree_util.keystr(kp)}: "
                       f"{s.shape}/{s.dtype} != {rs.shape}/{rs.dtype} "
                       f"(tp={ctx.tp} vs tp=1)")
    if len(got) != len(ref):
        bad.append(f"leaf count {len(got)} != {len(ref)} (tp={ctx.tp} "
                   f"vs tp=1)")
    if bad:
        raise AssertionError(
            f"config '{cfg.name}': global param pytree depends on the mesh "
            f"— violates the TP mesh-invariance contract (DESIGN.md §9):\n  "
            + "\n  ".join(bad[:20]))
