"""Operations and bytes a decode step and a fused_select call need,
from shapes alone. `model` is a configuration's "serve" block.

decode step, per generated token, useful work only:
  matmuls: 2 * (q, k, v, o, gate, up, down weights of every layer, plus
           the output head) FLOPs; biases, norms and rotary terms are
           left out (under 0.1% at these widths)
  attention at context c (positions attended): 4 * layers * c * heads
           * head_dim FLOPs (q.k and p.v)
fused_select, per call over B slots and V ids: it must read the logits,
  write the masked logits and read at least one packed mask row (V/32
  words) and the residue overlay (V/32 words) per slot. Its FLOPs (a
  compare and a select per id) are far below what the bytes cost, so
  its bound is bandwidth.
"""
from __future__ import annotations


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["d_model"] // model["num_heads"]


def layer_matmul_params(model: dict) -> int:
    d, h, k = model["d_model"], model["num_heads"], model["num_kv_heads"]
    dh = head_dim(model)
    attn = d * h * dh + 2 * d * k * dh + h * dh * d
    mlp = 3 * d * model["d_ff"]
    return attn + mlp


def matmul_params(model: dict) -> int:
    """Weights every generated token multiplies by (the head included,
    the embedding lookup not)."""
    return model["num_layers"] * layer_matmul_params(model) + \
        model["d_model"] * model["vocab_size"]


def decode_flops(model: dict, context: float) -> float:
    """FLOPs to generate one token that attends `context` positions."""
    attn = 4.0 * model["num_layers"] * context * model["num_heads"] * \
        head_dim(model)
    return 2.0 * matmul_params(model) + attn


def fused_select_bytes(slots: int, vocab: int, logit_bytes: int) -> int:
    words = -(-vocab // 32)
    return slots * (2 * vocab * logit_bytes + 2 * words * 4)


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """-> (least seconds, "flops" or "bytes": which bound it)."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
