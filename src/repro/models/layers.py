"""Layer kinds assembled into pattern blocks by decoder.py.

Each kind implements:
  init_<kind>(key, cfg, dtype) -> params
  <kind>_train(params, x, ctx)            -> x
  <kind>_prefill(params, x, ctx)          -> (x, cache)
  <kind>_decode(params, x, cache, ctx)    -> (x, cache)

ctx is a dict: {"positions": [B,S] or None, "pos": scalar decode position,
"image_embeds": [B,Ni,D] (vlm), "enc_out": [B,Se,D] (audio),
"cache_len": static cache length, "window": per-layer window override,
"layer": the scanned layer's index in dense decode, where the trunk
carries each group's stacked caches whole and `cache` is the stack}.

KV caches store rotated K plus a per-slot absolute-position array
(`kv_pos`, −1 = empty) so ring-buffer (sliding-window) and linear caches
share one masking rule: valid ⇔ 0 ≤ kv_pos ≤ q_pos (∧ q_pos − kv_pos <
window). A cache line holds the head dim zero-padded to whole 128-lane
tiles (`kv_width`); attention reads its first Dh lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import (apply_rope, attn_out, chunked_attention,
                     dense_init, ffn, init_attention, init_ffn, qkv_proj,
                     rms_norm, split_keys)
from ..kernels.paged_attention.ops import paged_attention
from ..kernels.paged_attention.ref import attend
from .moe import init_moe, moe_ffn
from .rglru import (init_rglru, init_rglru_cache, rglru_decode,
                    rglru_prefill, rglru_train)
from .ssm import init_ssm, init_ssm_cache, ssm_decode, ssm_prefill, ssm_train
from ..distributed.api import shard_hint


# ======================= attention with explicit cache =====================

def _window_of(cfg, ctx):
    return ctx.get("window", cfg.sliding_window)


def _cache_len(cfg, ctx, seq_len):
    w = _window_of(cfg, ctx)
    L = ctx.get("cache_len", seq_len)
    return min(L, w) if w else L


LANES = 128                     # a TPU vector register's minor dim


def kv_width(head_dim: int) -> int:
    """Head width of a KV cache line: Dh rounded up to whole lanes. A
    TPU lays out a [.., L, K, Dh] cache with Dh = 64 L-minor by default,
    and the decode step would then relayout whole caches around its
    in-place row writes; with a lane-multiple minor dim the default
    layout is the one the writes and attention use in place. At Dh = 64
    that doubles the cache's bytes, as any Dh-minor layout on the chip
    does."""
    return -(-head_dim // LANES) * LANES


def _to_width(x, width: int):
    """Zero-pad fresh K or V rows [.., Dh] to a cache line's width."""
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def init_kv_cache(cfg, batch, length, dtype, kv_heads=None):
    K = kv_heads or cfg.num_kv_heads
    W = kv_width(cfg.resolved_head_dim)
    return {
        "k": jnp.zeros((batch, length, K, W), dtype),
        "v": jnp.zeros((batch, length, K, W), dtype),
        "kv_pos": jnp.full((batch, length), -1, jnp.int32),
    }


def _self_attention_train(p, x, cfg, ctx, causal=True):
    B, S, D = x.shape
    q, k, v = qkv_proj(p, x, cfg)
    positions = ctx.get("positions")
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=causal,
                          window=_window_of(cfg, ctx), chunk=cfg.attn_chunk)
    return attn_out(p, o)


def _self_attention_prefill(p, x, cfg, ctx):
    """Returns (out, cache) — cache covers the last `cache_len` positions
    (ring layout slot = pos % cache_len)."""
    B, S, D = x.shape
    q, k, v = qkv_proj(p, x, cfg)
    positions = jnp.arange(S)[None, :]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=True,
                          window=_window_of(cfg, ctx), chunk=cfg.attn_chunk)
    L = _cache_len(cfg, ctx, S)
    cache = init_kv_cache(cfg, B, L, x.dtype)
    take = jnp.arange(L) + max(0, S - L)          # last L absolute positions
    slot = take % L
    # true_len < S marks bucket-padded prompt tail positions (the engine
    # pads prompts to power-of-two lengths so prefill jits once per
    # bucket, not once per length) as empty: their K/V are garbage the
    # ring overwrites later, and kv_pos = -1 keeps them unattendable.
    limit = jnp.minimum(S, jnp.asarray(ctx["true_len"], jnp.int32)) \
        if "true_len" in ctx else S
    kv_pos = jnp.broadcast_to(jnp.where(take < limit, take, -1)[None, :],
                              (B, L))
    W = cache["k"].shape[-1]
    cache = {
        "k": cache["k"].at[:, slot].set(
            _to_width(k[:, take], W).astype(cache["k"].dtype)),
        "v": cache["v"].at[:, slot].set(
            _to_width(v[:, take], W).astype(cache["v"].dtype)),
        "kv_pos": jnp.zeros((B, L), jnp.int32).at[:, slot].set(kv_pos),
    }
    return attn_out(p, o), cache


def _paged_attention_decode(p, x, cache, cfg, ctx):
    """Paged twin of `_self_attention_decode`: the cache is a global page
    pool {"k","v": [P, ps, K, Dh]} shared by every slot, and
    ctx["page_table"] [B, nP] (int32, -1 = unmapped) names each slot's
    pages. Span position i of slot b writes its K/V at
    (page_table[b, (pos+i)//ps], (pos+i)%ps) — a flat scatter; unmapped
    or feed_mask-gated positions drop — and attention reads back through
    the page table (`kernels.paged_attention`, bit-exact with the dense
    branch). Rejected speculative writes roll back exactly as in the
    dense path: positions beyond the commit frontier are masked
    (idx <= q_pos) and overwritten on re-feed."""
    B, S, D = x.shape
    kp, vp = cache["k"], cache["v"]                    # [P, ps, K, Dh]
    P, ps, K, Dh = kp.shape
    pos = jnp.broadcast_to(jnp.asarray(ctx["pos"], jnp.int32), (B,))
    qpos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B,S]
    q, k, v = qkv_proj(p, x, cfg)
    q = apply_rope(q, qpos, cfg.rope_theta)
    k = apply_rope(k, qpos, cfg.rope_theta)
    # pin the fresh K/V to the pool's kv-head sharding BEFORE the
    # scatter, so a sharded pool is updated shard-locally instead of
    # being gathered (identity outside a serving sharding ctx)
    k = shard_hint(k, "attn_kv")
    v = shard_hint(v, "attn_kv")
    pt = ctx["page_table"]
    page = jnp.take_along_axis(pt, qpos // ps, axis=1)             # [B,S]
    ok = page >= 0
    feed = ctx.get("feed_mask")
    if feed is not None:
        ok &= feed
    dest = jnp.where(ok, page * ps + qpos % ps, P * ps)  # OOB -> dropped
    flat = dest.reshape(-1)
    kp = kp.reshape(P * ps, K, Dh).at[flat].set(
        k.reshape(B * S, K, Dh).astype(kp.dtype),
        mode="drop").reshape(P, ps, K, Dh)
    vp = vp.reshape(P * ps, K, Dh).at[flat].set(
        v.reshape(B * S, K, Dh).astype(vp.dtype),
        mode="drop").reshape(P, ps, K, Dh)
    o = paged_attention(q, kp, vp, pt, pos)
    return attn_out(p, o), {"k": kp, "v": vp}


def _self_attention_decode(p, x, cache, cfg, ctx):
    """x [B,S,D] (S = 1 plain decode; S > 1 a speculative span);
    ctx['pos'] is a scalar or [B] int32 vector of absolute START
    positions (per-request positions in the serving engine) — span
    query i sits at absolute position pos + i.

    ctx['feed_mask'] [B,S] bool (optional) gates cache WRITES per span
    position: padding positions of a ragged span attend (their outputs
    are discarded by the caller) but never write, so rejected-draft /
    padding state can't leak into the cache. Writes from real positions
    at speculative offsets are naturally rolled back by the absolute-
    position masking rule (kv_pos <= q_pos) plus overwrite-on-reuse.

    When ctx carries a page table the slot's KV lives in the shared
    paged pool instead of a dense per-slot cache (docs/kv_paging.md)."""
    if "page_table" in ctx:
        return _paged_attention_decode(p, x, cache, cfg, ctx)
    B, S, D = x.shape
    pos = jnp.broadcast_to(jnp.asarray(ctx["pos"], jnp.int32), (B,))
    qpos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B,S]
    q, k, v = qkv_proj(p, x, cfg)
    q = apply_rope(q, qpos, cfg.rope_theta)
    k = apply_rope(k, qpos, cfg.rope_theta)
    kc, vc, kv_pos, cache = _write_rows(cache, k, v, qpos, ctx)

    # mask from absolute positions (per span query)
    w = _window_of(cfg, ctx)
    valid = (kv_pos[:, None, :] >= 0) & \
        (kv_pos[:, None, :] <= qpos[:, :, None])              # [B, S, L]
    if w:
        valid &= kv_pos[:, None, :] > (qpos[:, :, None] - w)
    # the paged path's attention math (bit-exact twins), on the lines'
    # first Dh lanes
    Dh = q.shape[-1]
    o = attend(q, kc[..., :Dh], vc[..., :Dh], valid).astype(x.dtype)
    return attn_out(p, o), cache


def _write_rows(stack, k, v, qpos, ctx):
    """In-place write into the stacked caches the decode trunk carries
    ({"k","v": [n, B, L, K, W], "kv_pos": [n, B, L]}): span position s
    of slot b lands at (layer, b, qpos[b, s] % L) by one scatter, and
    feed_mask-gated positions drop (an out-of-range line, as the paged
    path does). -> (layer's k and v on their first Dh lanes and kv_pos,
    after the write; the stacks). One dynamic slice takes the layer and
    the lanes, which the attention fusions then read in place; slicing
    the lanes off a taken layer would copy the layer first. Under a
    serving mesh the scatter stays shard-local: its indices run over the
    layer, slot and line dims and a kv-head-sharded cache splits only
    the rows it writes (docs/sharding.md)."""
    i = ctx["layer"]
    B, S = qpos.shape
    L, W = stack["k"].shape[2], stack["k"].shape[-1]
    slot = qpos % L
    feed = ctx.get("feed_mask")
    if feed is not None:
        slot = jnp.where(feed, slot, L)                 # OOB -> dropped
    b = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
    stack = {
        "k": stack["k"].at[i, b, slot].set(
            _to_width(k, W).astype(stack["k"].dtype), mode="drop"),
        "v": stack["v"].at[i, b, slot].set(
            _to_width(v, W).astype(stack["v"].dtype), mode="drop"),
        "kv_pos": stack["kv_pos"].at[i, b, slot].set(qpos, mode="drop"),
    }
    start = (i,) + (0,) * (stack["k"].ndim - 1)
    size = (1,) + stack["k"].shape[1:-1] + (k.shape[-1],)
    return (jax.lax.dynamic_slice(stack["k"], start, size)[0],
            jax.lax.dynamic_slice(stack["v"], start, size)[0],
            stack["kv_pos"][i], stack)


def _layer_of(cache, ctx):
    """Layer ctx["layer"]'s slice of a stacked cache the decode trunk
    carries."""
    return jax.tree.map(lambda a: a[ctx["layer"]], cache)


def _put_layer(stack, new, ctx):
    """Write a layer's new (small, recurrent) state back into the
    carried stack at ctx["layer"]."""
    return jax.tree.map(lambda a, n: a.at[ctx["layer"]].set(n), stack, new)


# ============================ layer kinds ==================================

# ---- "attn": self-attention + dense FFN (pre-norm residual) ----

def init_attn_layer(key, cfg, dtype):
    ks = split_keys(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "ffn": init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype),
    }


ZERO_AUX = {"lb": 0.0, "z": 0.0}


def _zero_aux():
    return {"lb": jnp.zeros((), jnp.float32), "z": jnp.zeros((), jnp.float32)}


def attn_train(p, x, cfg, ctx):
    x = x + _self_attention_train(p["attn"], rms_norm(x, p["ln1"],
                                                      cfg.norm_eps), cfg, ctx)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), _zero_aux()


def attn_prefill(p, x, cfg, ctx):
    o, cache = _self_attention_prefill(p["attn"],
                                       rms_norm(x, p["ln1"], cfg.norm_eps),
                                       cfg, ctx)
    x = x + o
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), cache


def attn_decode(p, x, cache, cfg, ctx):
    o, cache = _self_attention_decode(p["attn"],
                                      rms_norm(x, p["ln1"], cfg.norm_eps),
                                      cache, cfg, ctx)
    x = x + o
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), cache


# ---- "moe": self-attention + MoE FFN ----

def init_moe_layer(key, cfg, dtype):
    ks = split_keys(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "moe": init_moe(ks[1], cfg, dtype),
    }


def moe_train(p, x, cfg, ctx):
    x = x + _self_attention_train(p["attn"], rms_norm(x, p["ln1"],
                                                      cfg.norm_eps), cfg, ctx)
    y, aux = moe_ffn(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return shard_hint(x + y, "act_bsd"), {"lb": aux["lb_loss"],
                                          "z": aux["z_loss"]}


def moe_prefill(p, x, cfg, ctx):
    o, cache = _self_attention_prefill(p["attn"],
                                       rms_norm(x, p["ln1"], cfg.norm_eps),
                                       cfg, ctx)
    x = x + o
    y, _ = moe_ffn(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return shard_hint(x + y, "act_bsd"), cache


def moe_decode(p, x, cache, cfg, ctx):
    o, cache = _self_attention_decode(p["attn"],
                                      rms_norm(x, p["ln1"], cfg.norm_eps),
                                      cache, cfg, ctx)
    x = x + o
    y, _ = moe_ffn(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return shard_hint(x + y, "act_bsd"), cache


# ---- "cross": cross-attention to image/encoder tokens + FFN (VLM) ----

def init_cross_layer(key, cfg, dtype):
    ks = split_keys(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "ffn": init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype),
        "gate": jnp.zeros((1,), dtype),      # tanh-gated residual
    }


def _cross_kv(p, mem, cfg):
    B, Sm, D = mem.shape
    K, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    k = mem @ p["wk"]
    v = mem @ p["wv"]
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    return k.reshape(B, Sm, K, Dh), v.reshape(B, Sm, K, Dh)


def _cross_attention(p, x, k, v, cfg):
    B, S, D = x.shape
    H, Dh = cfg.num_heads, cfg.resolved_head_dim
    q = (x @ p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, S, H, Dh)
    o = chunked_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
    return attn_out(p, o)


def cross_train(p, x, cfg, ctx):
    mem = ctx["image_embeds"] if "image_embeds" in ctx else ctx["enc_out"]
    k, v = _cross_kv(p["attn"], mem, cfg)
    g = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype)
    x = x + g * _cross_attention(p["attn"],
                                 rms_norm(x, p["ln1"], cfg.norm_eps),
                                 k, v, cfg)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), _zero_aux()


def cross_prefill(p, x, cfg, ctx):
    mem = ctx["image_embeds"] if "image_embeds" in ctx else ctx["enc_out"]
    k, v = _cross_kv(p["attn"], mem, cfg)
    g = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype)
    x = x + g * _cross_attention(p["attn"],
                                 rms_norm(x, p["ln1"], cfg.norm_eps),
                                 k, v, cfg)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), {"k": k, "v": v}


def cross_decode(p, x, cache, cfg, ctx):
    g = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype)
    mem = _layer_of(cache, ctx)
    x = x + g * _cross_attention(p["attn"],
                                 rms_norm(x, p["ln1"], cfg.norm_eps),
                                 mem["k"], mem["v"], cfg)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return x, cache


# ---- "rec": RG-LRU recurrent block + FFN (RecurrentGemma) ----

def init_rec_layer(key, cfg, dtype):
    ks = split_keys(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "rec": init_rglru(ks[0], cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "ffn": init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype),
    }


def rec_train(p, x, cfg, ctx):
    x = x + rglru_train(p["rec"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), _zero_aux()


def rec_prefill(p, x, cfg, ctx):
    o, cache = rglru_prefill(p["rec"], rms_norm(x, p["ln1"], cfg.norm_eps),
                             cfg)
    x = x + o
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), cache


def rec_decode(p, x, cache, cfg, ctx):
    o, new = rglru_decode(p["rec"], rms_norm(x, p["ln1"], cfg.norm_eps),
                          _layer_of(cache, ctx), cfg)
    x = x + o
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return x, _put_layer(cache, new, ctx)


# ---- "ssm": Mamba2 block (no separate FFN; norm + SSD + residual) ----

def init_ssm_layer(key, cfg, dtype):
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ssm": init_ssm(key, cfg, dtype),
    }


def ssm_layer_train(p, x, cfg, ctx):
    return shard_hint(
        x + ssm_train(p["ssm"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg),
        "act_bsd"), _zero_aux()


def ssm_layer_prefill(p, x, cfg, ctx):
    o, cache = ssm_prefill(p["ssm"], rms_norm(x, p["ln1"], cfg.norm_eps),
                           cfg)
    return shard_hint(x + o, "act_bsd"), cache


def ssm_layer_decode(p, x, cache, cfg, ctx):
    o, new = ssm_decode(p["ssm"], rms_norm(x, p["ln1"], cfg.norm_eps),
                        _layer_of(cache, ctx), cfg)
    return x + o, _put_layer(cache, new, ctx)


# ---- "enc": non-causal encoder layer (Whisper encoder) ----

def init_enc_layer(key, cfg, dtype):
    return init_attn_layer(key, cfg, dtype)


def enc_train(p, x, cfg, ctx):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = qkv_proj(p["attn"], h, cfg)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
    x = x + attn_out(p["attn"], o)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), _zero_aux()


# ---- "dec": decoder layer with self + cross (Whisper decoder) ----

def init_dec_layer(key, cfg, dtype):
    ks = split_keys(key, 3)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "lnx": jnp.ones((cfg.d_model,), dtype),
        "xattn": init_attention(ks[1], cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "ffn": init_ffn(ks[2], cfg.d_model, cfg.d_ff, dtype),
    }


def dec_train(p, x, cfg, ctx):
    x = x + _self_attention_train(p["attn"],
                                  rms_norm(x, p["ln1"], cfg.norm_eps),
                                  cfg, ctx)
    k, v = _cross_kv(p["xattn"], ctx["enc_out"], cfg)
    x = x + _cross_attention(p["xattn"], rms_norm(x, p["lnx"], cfg.norm_eps),
                             k, v, cfg)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), _zero_aux()


def dec_prefill(p, x, cfg, ctx):
    o, self_cache = _self_attention_prefill(
        p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg, ctx)
    x = x + o
    k, v = _cross_kv(p["xattn"], ctx["enc_out"], cfg)
    x = x + _cross_attention(p["xattn"], rms_norm(x, p["lnx"], cfg.norm_eps),
                             k, v, cfg)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return shard_hint(x, "act_bsd"), {"self": self_cache,
                                      "cross": {"k": k, "v": v}}


def dec_decode(p, x, cache, cfg, ctx):
    o, self_cache = _self_attention_decode(
        p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cache["self"],
        cfg, ctx)
    x = x + o
    mem = _layer_of(cache["cross"], ctx)
    x = x + _cross_attention(p["xattn"], rms_norm(x, p["lnx"], cfg.norm_eps),
                             mem["k"], mem["v"], cfg)
    x = x + ffn(p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return x, {"self": self_cache, "cross": cache["cross"]}


# ============================ registry =====================================

KIND_INIT = {
    "attn": init_attn_layer,
    "moe": init_moe_layer,
    "cross": init_cross_layer,
    "rec": init_rec_layer,
    "ssm": init_ssm_layer,
    "enc": init_enc_layer,
    "dec": init_dec_layer,
}
KIND_TRAIN = {
    "attn": attn_train,
    "moe": moe_train,
    "cross": cross_train,
    "rec": rec_train,
    "ssm": ssm_layer_train,
    "enc": enc_train,
    "dec": dec_train,
}
KIND_PREFILL = {
    "attn": attn_prefill,
    "moe": moe_prefill,
    "cross": cross_prefill,
    "rec": rec_prefill,
    "ssm": ssm_layer_prefill,
    "dec": dec_prefill,
}
KIND_DECODE = {
    "attn": attn_decode,
    "moe": moe_decode,
    "cross": cross_decode,
    "rec": rec_decode,
    "ssm": ssm_layer_decode,
    "dec": dec_decode,
}
