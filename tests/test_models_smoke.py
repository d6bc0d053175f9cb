"""Per-architecture smoke tests (assignment requirement): a REDUCED
variant of each assigned family (2 layers, d_model<=512, <=4 experts)
runs one forward/train step and one prefill+decode step on CPU, asserting
output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models.model import build_model

B, S = 2, 32


def _batch(cfg, rng):
    ks = jax.random.split(rng, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
    }
    if cfg.arch_type == "vlm":
        batch["image_embeds"] = jax.random.normal(
            ks[2], (B, cfg.num_image_tokens, cfg.d_model),
            dtype=jnp.bfloat16)
    if cfg.arch_type == "audio":
        batch["frames"] = jax.random.normal(
            ks[2], (B, cfg.audio_frames, cfg.d_model), dtype=jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    batch = _batch(cfg, jax.random.PRNGKey(1))
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss, has_aux=True))(params, batch)
    assert np.isfinite(float(loss)), (arch, float(loss))
    # at least one grad must be nonzero and all finite
    leaves = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(l, dtype=np.float32)))
               for l in leaves), arch
    assert any(float(jnp.abs(l.astype(jnp.float32)).max()) > 0
               for l in leaves), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_smoke(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, caches = jax.jit(model.prefill)(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, dtype=np.float32)))
    tok = jnp.argmax(logits[:, -1], axis=-1)
    step = jax.jit(model.decode_step)
    lg, caches = step(params, caches, tok, jnp.full((B,), S, jnp.int32))
    assert lg.shape == (B, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(lg, dtype=np.float32)))
    lg2, caches = step(params, caches,
                       jnp.argmax(lg, -1), jnp.full((B,), S + 1, jnp.int32))
    assert np.all(np.isfinite(np.asarray(lg2, dtype=np.float32)))


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-370m",
                                  "recurrentgemma-9b", "whisper-base",
                                  "qwen3-moe-30b-a3b",
                                  "llama-3.2-vision-90b"])
def test_decode_matches_train_forward(arch):
    """Prefill+decode of token t must equal the train forward's logits at
    the same position (cache correctness). SSM recurrences accumulate
    bf16 rounding differently step-by-step vs chunked, hence the wider
    tolerance there (exactness in f32 is covered by test_ssm_numerics)."""
    tol = 0.5 if arch in ("mamba2-370m", "recurrentgemma-9b") else 0.08
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    full_logits, _ = jax.jit(model.train_logits)(params, batch)

    toks = batch["tokens"]
    pre_batch = dict(batch, tokens=toks[:, : S - 4])
    logits, caches = jax.jit(
        lambda p, b: model.prefill(p, b, cache_len=S))(params, pre_batch)
    np.testing.assert_allclose(
        np.asarray(logits[:, -1], np.float32),
        np.asarray(full_logits[:, S - 5], np.float32),
        rtol=tol, atol=tol)
    step = jax.jit(model.decode_step)
    for i in range(S - 4, S):
        lg, caches = step(params, caches, toks[:, i],
                          jnp.full((B,), i, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(lg, np.float32),
            np.asarray(full_logits[:, i], np.float32),
            rtol=tol, atol=tol, err_msg=f"{arch} step {i}")


def test_init_decode_caches_marks_lines_empty():
    """Fresh decode caches hold no position: every kv_pos is -1, so a
    span fed into them (the engine's dense prefill) attends only to what
    it wrote."""
    model = build_model(get_config("qwen1.5-0.5b").reduced())
    caches = model.init_decode_caches(2, 16)
    kv_pos = [np.asarray(c["kv_pos"]) for group in caches for c in group]
    assert kv_pos and all((k == -1).all() for k in kv_pos)


def test_sliding_window_decode_ring_buffer():
    cfg = get_config("internlm2-1.8b").reduced(sliding_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, caches = jax.jit(model.prefill)(params, batch)
    # cache length must be the window, not S
    k = caches[0][0]["k"]
    assert k.shape[2] == 8  # [count, B, L=window, K, Dh]
    step = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1], -1)
    for i in range(S, S + 12):
        lg, caches = step(params, caches, tok, jnp.full((B,), i, jnp.int32))
        tok = jnp.argmax(lg, -1)
        assert np.all(np.isfinite(np.asarray(lg, np.float32)))


def test_ssm_numerics_f32_exact():
    """Chunked SSD == naive sequential recurrence in f32 (oracle check)."""
    from repro.models.ssm import (init_ssm, init_ssm_cache, ssm_decode,
                                  ssm_prefill, ssm_train)
    cfg = get_config("mamba2-370m").reduced(dtype="float32")
    p = init_ssm(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                          dtype=jnp.float32) * 0.5
    y_train = ssm_train(p, x, cfg)
    cache = init_ssm_cache(cfg, 2, jnp.float32)
    ys = []
    for t in range(64):
        y, cache = ssm_decode(p, x[:, t:t + 1], cache, cfg)
        ys.append(y)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_train), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-4)
    y_pre, c2 = ssm_prefill(p, x[:, :32], cfg)
    y_d, _ = ssm_decode(p, x[:, 32:33], c2, cfg)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_train[:, 32:33]),
                               rtol=1e-4, atol=1e-4)


def test_rglru_numerics_f32_exact():
    from repro.models.rglru import (init_rglru, init_rglru_cache,
                                    rglru_decode, rglru_prefill, rglru_train)
    cfg = get_config("recurrentgemma-9b").reduced(dtype="float32")
    p = init_rglru(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, cfg.d_model),
                          dtype=jnp.float32) * 0.5
    y_train = rglru_train(p, x, cfg)
    cache = init_rglru_cache(cfg, 2, jnp.float32)
    ys = []
    for t in range(48):
        y, cache = rglru_decode(p, x[:, t:t + 1], cache, cfg)
        ys.append(y)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_train), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-4)
    y_pre, c2 = rglru_prefill(p, x[:, :20], cfg)
    y_d, _ = rglru_decode(p, x[:, 20:21], c2, cfg)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_train[:, 20:21]),
                               rtol=1e-4, atol=1e-4)


def test_chunked_attention_matches_direct():
    from repro.models.common import chunked_attention
    rng = jax.random.PRNGKey(0)
    B, Sq, Sk, H, K, Dh = 2, 37, 37, 6, 3, 16
    q = jax.random.normal(rng, (B, Sq, H, Dh), dtype=jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Sk, K, Dh),
                          dtype=jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Sk, K, Dh),
                          dtype=jnp.float32)
    direct = chunked_attention(q, k, v, causal=True, chunk=4096)
    chunked = chunked_attention(q, k, v, causal=True, chunk=8)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(chunked),
                               rtol=2e-5, atol=2e-5)
    # sliding window agreement
    d2 = chunked_attention(q, k, v, causal=True, window=9, chunk=4096)
    c2 = chunked_attention(q, k, v, causal=True, window=9, chunk=8)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(c2),
                               rtol=2e-5, atol=2e-5)
