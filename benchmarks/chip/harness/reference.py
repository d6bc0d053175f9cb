"""Plain float32 forward pass of a dense decoder, and its fp8 control.

The published architecture of Qwen1.5 and of llama-style decoders
such as DeepSeek-Coder: token embedding; per layer a pre-norm
residual block of RMSNorm, grouped-query attention with rotary position
embedding (the half-split form) and optional q/k/v biases, then RMSNorm
and a SwiGLU MLP; a final RMSNorm and the output head (tied to the
embedding or not). No cache, no batching, no kernels: one causal pass
over a whole sequence, every matmul at `Precision.HIGHEST`.

It reads the benchmark's weights (harness/weights.py) by leaf name and
imports nothing of the program. Layers run under one `lax.scan` and
cast their own bf16 weights to float32 inside the body, so only one
layer's float32 image exists at a time.

`precision="fp8"` is the control: every matmul operand (weight by output
channel, activation by row) rounded to float8_e4m3fn with a max-abs
scale, the rest as above. It is the step below the configuration's
stated bf16 that a later change might take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8_e4m3fn with a max-abs scale along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control):
    """x [..., I] @ w [I, O] in float32; the control rounds both."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x [S, H, Dh] at positions 0..S-1, half-split rotation."""
    S, _, dh = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2) / dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * \
        jnp.asarray(freqs, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, p, m, control):
    S = h.shape[0]
    H, K, Dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    a = p["attn"]
    x = _rms(h, p["ln1"], m["norm_eps"])
    q, k, v = _mm(x, a["wq"], control), _mm(x, a["wk"], control), \
        _mm(x, a["wv"], control)
    if "bq" in a:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q = _rope(q.reshape(S, H, Dh), m["rope_theta"])
    k = _rope(k.reshape(S, K, Dh), m["rope_theta"])
    v = v.reshape(S, K, Dh)
    k = jnp.repeat(k, H // K, axis=1)           # head h reads kv h // G
    v = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(Dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    h = h + _mm(o.reshape(S, H * Dh), a["wo"], control)
    f = p["ffn"]
    x = _rms(h, p["ln2"], m["norm_eps"])
    g = jax.nn.silu(_mm(x, f["w_gate"], control)) * _mm(x, f["w_up"],
                                                         control)
    return h + _mm(g, f["w_down"], control)


@functools.partial(jax.jit, static_argnames=("model", "control"))
def _logits(weights, tokens, want, model, control):
    m = dict(model)
    eb = weights["embed_block"]
    h = eb["embed"][tokens].astype(jnp.float32)
    (group,) = weights["groups"]
    (stack,) = group

    def body(h, p):
        return _layer(h, p, m, control), None

    h, _ = jax.lax.scan(body, h, stack)
    x = _rms(h[want], eb["final_norm"], m["norm_eps"])
    head = eb["lm_head"] if "lm_head" in eb else eb["embed"].T
    return _mm(x, head, control)


def logits_at(weights, model: dict, tokens, want, seq_len: int,
              n_want: int, control: bool = False):
    """float32 logits [len(want), V] of the next token after each
    position in `want`, from one causal pass over `tokens`. Both are
    padded at the end (which a causal pass never reads back) to the fixed
    `seq_len` and `n_want`, so one program serves every request."""
    tokens = np.asarray(tokens, np.int32)
    want = np.asarray(want, np.int32)
    S, M = seq_len, n_want
    if len(tokens) > S or len(want) > M:
        raise ValueError(f"{len(tokens)} tokens / {len(want)} positions "
                         f"exceed the reference's {S} / {M}")
    tp = np.zeros(S, np.int32)
    tp[:len(tokens)] = tokens
    wp = np.zeros(M, np.int32)
    wp[:len(want)] = want
    key = tuple(sorted((k, v) for k, v in model.items()))
    out = _logits(weights, jnp.asarray(tp), jnp.asarray(wp), key, control)
    return np.asarray(out)[:len(want)]
