"""Model assembly: pattern-block layer groups scanned with lax.scan.

Layers with identical structure are stacked ([count, ...] params) and run
under `lax.scan`, so HLO size is independent of depth (compile-time and
memory hygiene for the 100-layer dry-run configs). Heterogeneous stacks
(hybrid 2:1 recurrent:attention, VLM every-5th cross-attention, MoE with
leading dense layers) become a short list of homogeneous *groups*, each
scanning a fixed intra-block pattern.

Public API (all pure functions over a params pytree):
  model.init(rng)                               -> params
  model.train_logits(params, batch)             -> (logits [B,S,V], aux)
  model.loss(params, batch)                     -> (scalar, metrics)
  model.prefill(params, batch)                  -> (logits [B,S,V], caches)
  model.decode_step(params, caches, token, pos) -> (logits [B,V], caches)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import (cross_entropy_loss, dense_init, dtype_of, embed_tokens,
                     init_embed, lm_logits, rms_norm, split_keys)
from .config import ModelConfig
from .layers import KIND_DECODE, KIND_INIT, KIND_PREFILL, KIND_TRAIN
from ..distributed.api import shard_hint

LB_COEF = 0.01
Z_COEF = 0.001


def layer_groups(cfg: ModelConfig):
    """-> list of (pattern tuple, count). Decoder-side stack."""
    L = cfg.num_layers
    at = cfg.arch_type
    if at == "dense":
        return [(("attn",), L)]
    if at == "moe":
        gs = []
        fd = cfg.first_dense_layers
        if fd:
            gs.append((("attn",), fd))
        gs.append((("moe",), L - fd))
        return gs
    if at == "ssm":
        return [(("ssm",), L)]
    if at == "hybrid":
        pat = tuple(cfg.block_pattern)
        n, rem = divmod(L, len(pat))
        gs = [(pat, n)] if n else []
        if rem:
            gs.append((pat[:rem], 1))
        return gs
    if at == "vlm":
        e = cfg.cross_attn_every
        pat = ("attn",) * (e - 1) + ("cross",)
        n, rem = divmod(L, e)
        gs = [(pat, n)] if n else []
        if rem:
            gs.append((("attn",) * rem, 1))
        return gs
    if at == "audio":
        return [(("dec",), L)]
    raise ValueError(at)


def _init_group(key, pattern, count, cfg, dtype):
    """-> tuple over pattern positions of stacked param trees [count,...]."""
    out = []
    for j, kind in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(key, j), count)
        out.append(jax.vmap(
            lambda k: KIND_INIT[kind](k, cfg, dtype))(keys))
    return tuple(out)


def _sum_aux(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


@dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------ init ---------------------------------
    def init(self, rng):
        cfg = self.cfg
        dtype = dtype_of(cfg)
        ks = split_keys(rng, 4)
        params = {"embed_block": init_embed(ks[0], cfg, dtype)}
        params["groups"] = [
            _init_group(jax.random.fold_in(ks[1], gi), pat, count, cfg, dtype)
            for gi, (pat, count) in enumerate(layer_groups(cfg))
        ]
        if cfg.arch_type == "audio":
            enc_keys = jax.random.fold_in(ks[2], 0)
            params["encoder"] = _init_group(enc_keys, ("enc",),
                                            cfg.encoder_layers, cfg, dtype)
        return params

    def abstract_params(self, rng=None):
        """ShapeDtypeStruct params (no allocation) for AOT lowering."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, rng)

    # --------------------------- encoder (audio) --------------------------
    def _encode_frames(self, params, frames):
        cfg = self.cfg
        x = frames
        def body(x, pslice):
            y, _ = KIND_TRAIN["enc"](pslice, x, cfg, {})
            return y, None
        x, _ = jax.lax.scan(body, x, params["encoder"][0])
        return x

    def _base_ctx(self):
        ctx = {}
        if self.cfg.arch_type == "hybrid":
            # hybrid attention layers are local (RecurrentGemma 1:2)
            ctx["window"] = self.cfg.local_window
        return ctx

    def _ctx_from_batch(self, params, batch):
        ctx = self._base_ctx()
        if self.cfg.arch_type == "vlm":
            ctx["image_embeds"] = batch["image_embeds"]
        if self.cfg.arch_type == "audio":
            ctx["enc_out"] = self._encode_frames(params, batch["frames"])
        return ctx

    # ------------------------------ train --------------------------------
    def _trunk(self, params, batch):
        """Embed + layer stacks -> (hidden [B,S,D], aux losses)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        ctx = self._ctx_from_batch(params, batch)
        x = embed_tokens(params["embed_block"], tokens)
        x = shard_hint(x, "act_bsd")
        aux = {"lb": jnp.zeros((), jnp.float32),
               "z": jnp.zeros((), jnp.float32)}
        for (pat, count), gp in zip(layer_groups(cfg), params["groups"]):
            def body(x, pslices, pat=pat):
                # barrier: without it XLA hoists the first bf16->f32
                # convert of x out of the backward while-loop, material-
                # izing an f32 copy of the whole [L,B,S,D] residual stack
                # (observed 12.9 GB/device on internlm2 train_4k).
                x = jax.lax.optimization_barrier(x)
                a = {"lb": jnp.zeros((), jnp.float32),
                     "z": jnp.zeros((), jnp.float32)}
                for j, kind in enumerate(pat):
                    x, aj = KIND_TRAIN[kind](pslices[j], x, cfg, ctx)
                    a = _sum_aux(a, aj)
                return x, a
            if cfg.remat:
                body = jax.checkpoint(body)
            x, auxs = jax.lax.scan(body, x, gp)
            aux = _sum_aux(aux, jax.tree.map(jnp.sum, auxs))
        return x, aux

    def train_logits(self, params, batch):
        x, aux = self._trunk(params, batch)
        logits = lm_logits(params["embed_block"], x, self.cfg)
        return shard_hint(logits, "logits_bsv"), aux

    def loss(self, params, batch, seq_chunk: int = 1024):
        """Sequence-chunked softmax cross-entropy: per-chunk logits are
        (re)computed under jax.checkpoint, so the [B,S,V] logits tensor is
        never materialized (memory analysis showed it dominating trainer
        HBM for the 150k-vocab archs; see EXPERIMENTS.md §Perf)."""
        cfg = self.cfg
        x, aux = self._trunk(params, batch)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        B, S, D = x.shape
        C = min(seq_chunk, S)
        eb = params["embed_block"]
        if S % C != 0:
            logits = shard_hint(lm_logits(eb, x, cfg), "logits_bsv")
            ce = cross_entropy_loss(logits, labels, mask)
        else:
            n = S // C

            @jax.checkpoint
            def chunk_nll(xc, lc, mc):
                logits = lm_logits(eb, xc, cfg)
                logits = shard_hint(logits, "logits_bsv").astype(jnp.float32)
                logz = jax.nn.logsumexp(logits, axis=-1)
                onehot = (jnp.arange(cfg.vocab_size, dtype=lc.dtype)
                          == lc[..., None])
                gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
                nll = (logz - gold) * mc
                return nll.sum(), mc.sum()

            def body(carry, args):
                tot, cnt = carry
                s, c = chunk_nll(*args)
                return (tot + s, cnt + c), None

            xs = (x.reshape(B, n, C, D).swapaxes(0, 1),
                  labels.reshape(B, n, C).swapaxes(0, 1),
                  (mask if mask is not None else
                   jnp.ones((B, S), jnp.float32)).reshape(
                      B, n, C).swapaxes(0, 1))
            (tot, cnt), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), xs)
            ce = tot / jnp.maximum(cnt, 1.0)
        total = ce + LB_COEF * aux["lb"] + Z_COEF * aux["z"]
        return total, {"ce": ce, "lb": aux["lb"], "z": aux["z"]}

    # ----------------------------- prefill -------------------------------
    def prefill(self, params, batch, cache_len=None, true_len=None):
        """true_len (traced scalar, optional): number of real prompt
        tokens when `tokens` is padded to a jit bucket length — padded
        positions get kv_pos = -1 so they can never be attended."""
        cfg = self.cfg
        tokens = batch["tokens"]
        ctx = self._ctx_from_batch(params, batch)
        if cache_len is not None:
            ctx["cache_len"] = cache_len
        if true_len is not None:
            ctx["true_len"] = true_len
        x = embed_tokens(params["embed_block"], tokens)
        x = shard_hint(x, "act_bsd")
        caches = []
        for (pat, count), gp in zip(layer_groups(cfg), params["groups"]):
            def body(x, pslices, pat=pat):
                cs = []
                for j, kind in enumerate(pat):
                    x, c = KIND_PREFILL[kind](pslices[j], x, cfg, ctx)
                    cs.append(c)
                return x, tuple(cs)
            x, group_cache = jax.lax.scan(body, x, gp)
            caches.append(group_cache)
        logits = lm_logits(params["embed_block"], x, cfg)
        return shard_hint(logits, "logits_bsv"), caches

    # ------------------------------ decode -------------------------------
    def _decode_trunk(self, params, caches, tokens, ctx):
        """Shared decode body: embed [B,S] tokens, run every layer group's
        KIND_DECODE under lax.scan against the caches -> ([B,S,V], caches).

        Dense caches ride in the scan's carry whole: layer i writes only
        its new rows into the stacked cache (ctx["layer"] = i;
        `layers._self_attention_decode`), so a donated cache is updated
        in place and only attention reads it. Paged pools ride as the
        scan's xs/ys, each layer's pool written by its own scatter."""
        cfg = self.cfg
        x = embed_tokens(params["embed_block"], tokens)
        # pin the residual stream's sharding before the layer scan (same
        # hint train/prefill apply): under the serving rules this
        # gathers the vocab-sharded embedding lookup back to replicated
        # exactly once, instead of leaving GSPMD to re-decide inside the
        # scanned layer body (docs/sharding.md)
        x = shard_hint(x, "act_bsd")
        paged = "page_table" in ctx
        new_caches = []
        for (pat, count), gp, gc in zip(layer_groups(cfg), params["groups"],
                                        caches):
            def run(x, pslices, cslices, lctx, pat=pat):
                ncs = []
                for j, kind in enumerate(pat):
                    x, nc = KIND_DECODE[kind](pslices[j], x, cslices[j],
                                              cfg, lctx)
                    ncs.append(nc)
                return x, tuple(ncs)
            if paged:
                x, ngc = jax.lax.scan(
                    lambda x, sl, run=run: run(x, *sl, ctx), x, (gp, gc))
            else:
                def body(carry, sl, run=run):
                    (x, cs), (pslices, i) = carry, sl
                    return run(x, pslices, cs, dict(ctx, layer=i)), None
                (x, ngc), _ = jax.lax.scan(
                    body, (x, gc), (gp, jnp.arange(count, dtype=jnp.int32)))
            new_caches.append(ngc)
        return lm_logits(params["embed_block"], x, cfg), new_caches

    def decode_step(self, params, caches, token, pos, batch_ctx=None):
        """token [B] int32, pos [B] or scalar int32 -> (logits [B,V], caches)."""
        ctx = self._base_ctx()
        ctx.update(batch_ctx or {})
        ctx["pos"] = pos
        logits, new_caches = self._decode_trunk(params, caches,
                                                token[:, None], ctx)
        return shard_hint(logits[:, 0], "logits_bv"), new_caches

    @property
    def prefill_padding_safe(self) -> bool:
        """True iff prefill tolerates a zero-padded prompt tail under
        `true_len` masking (the serving engine's jit-bucketing). Cache
        entries of attention kinds are per-position and masked via
        kv_pos; recurrent kinds (rec/ssm) fold the padded tail into
        their carried state, so they must be prefilled at exact
        length."""
        return all(kind not in ("rec", "ssm")
                   for pat, _ in layer_groups(self.cfg) for kind in pat)

    @property
    def supports_span_decode(self) -> bool:
        """True iff every decode layer kind is position-addressed (KV
        cache keyed by absolute position), so a multi-token span can be
        fed in one call and rejected speculative writes roll back via the
        kv_pos <= q_pos masking rule. Recurrent kinds (rec/ssm) carry
        unaddressed state and cannot rewind; cross/dec need side inputs."""
        return all(kind in ("attn", "moe")
                   for pat, _ in layer_groups(self.cfg) for kind in pat)

    def decode_span(self, params, caches, tokens, pos, feed_mask=None,
                    batch_ctx=None):
        """Speculative span decode: tokens [B,S] at absolute positions
        pos[b] + i -> (logits [B,S,V], caches). One fused device call
        scores a whole draft window (jump-forward feed + draft-verify),
        replacing S sequential decode_step round-trips. feed_mask [B,S]
        bool gates per-position cache writes for ragged spans (see
        layers._self_attention_decode). Requires supports_span_decode."""
        ctx = self._base_ctx()
        ctx.update(batch_ctx or {})
        ctx["pos"] = pos
        if feed_mask is not None:
            ctx["feed_mask"] = feed_mask
        logits, new_caches = self._decode_trunk(params, caches, tokens, ctx)
        return shard_hint(logits, "logits_bsv"), new_caches


    # ------------------------- cache construction ------------------------
    def init_decode_caches(self, batch_size: int, cache_len: int):
        """Zero caches shaped for decode (used by the decode dry-run shapes
        and by the serving engine's slot allocator)."""
        from .layers import init_kv_cache
        from .rglru import init_rglru_cache
        from .ssm import init_ssm_cache
        cfg = self.cfg
        dtype = dtype_of(cfg)
        K, Dh = cfg.num_kv_heads, cfg.resolved_head_dim

        def one(kind):
            w = cfg.local_window if cfg.arch_type == "hybrid" else \
                cfg.sliding_window
            L = min(cache_len, w) if w else cache_len
            if kind in ("attn", "moe"):
                return init_kv_cache(cfg, batch_size, L, dtype)
            if kind == "rec":
                return init_rglru_cache(cfg, batch_size, dtype)
            if kind == "ssm":
                return init_ssm_cache(cfg, batch_size, dtype)
            if kind == "cross":
                Ni = cfg.num_image_tokens
                return {"k": jnp.zeros((batch_size, Ni, K, Dh), dtype),
                        "v": jnp.zeros((batch_size, Ni, K, Dh), dtype)}
            if kind == "dec":
                Sa = cfg.audio_frames
                return {
                    "self": init_kv_cache(cfg, batch_size, cache_len, dtype),
                    "cross": {
                        "k": jnp.zeros((batch_size, Sa, K, Dh), dtype),
                        "v": jnp.zeros((batch_size, Sa, K, Dh), dtype)},
                }
            raise ValueError(kind)

        caches = []
        for pat, count in layer_groups(cfg):
            # broadcast, not zeros: an empty line's kv_pos is -1
            group = tuple(
                jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (count,) + a.shape),
                    one(kind))
                for kind in pat)
            caches.append(group)
        return caches

    def init_paged_caches(self, num_pages: int, page_size: int):
        """Global paged KV pool for the serving engine's paged mode
        (docs/kv_paging.md): every attention layer holds
        {"k","v": [count, num_pages, page_size, K, Dh]} shared across
        all decode slots; per-slot page tables ride in via
        ctx["page_table"] on each decode/span call. Requires
        position-addressed, window-free attention throughout."""
        cfg = self.cfg
        if not self.supports_span_decode:
            raise ValueError(
                "paged KV caches need position-addressed decode caches "
                "(attn/moe layer kinds); this arch has recurrent or "
                "side-input state")
        if cfg.sliding_window:
            raise ValueError(
                "paged KV caches do not support sliding-window attention")
        dtype = dtype_of(cfg)
        K, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
        caches = []
        for pat, count in layer_groups(cfg):
            group = tuple(
                {"k": jnp.zeros((count, num_pages, page_size, K, Dh),
                                dtype),
                 "v": jnp.zeros((count, num_pages, page_size, K, Dh),
                                dtype)}
                for _ in pat)
            caches.append(group)
        return caches


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
