"""Seeded weights, made on the device in one jitted call.

The program lays its parameters out as a tree (embedding block, then
stacked per-layer groups); `jax.eval_shape` of its init gives that tree's
shapes and dtypes without computing anything. The values are the
benchmark's own: every leaf is drawn here from the run's seed, directly
in the served dtype, so the reference and the program read the same
numbers and neither takes them from the other.

Scales, by leaf name:
  matrices (wq wk wv wo w_gate w_up w_down): normal / sqrt(fan_in)
  embed, lm_head: normal * 4 / sqrt(d_model), so logits have a spread
      of about 4 whether or not the head is tied to the embedding
  qkv biases: normal * 0.2
  norm weights (ln1 ln2 final_norm): 1 + normal * 0.1
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = frozenset(("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"))
BIASES = frozenset(("bq", "bk", "bv"))
NORMS = frozenset(("ln1", "ln2", "final_norm"))


def _leaf_name(path) -> str:
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            return str(k.key)
    raise ValueError(f"parameter at {path} has no name")


def seed_words(seed: int) -> np.ndarray:
    """Any whole number (seeds may exceed 32 bits) -> two uint32."""
    s = int(seed) & ((1 << 64) - 1)
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def maker(shapes, d_model: int):
    """-> jitted fn(seed_words) building a tree shaped like `shapes`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    head_scale = 4.0 / math.sqrt(d_model)

    def leaf(key, path, sd):
        name = _leaf_name(path)
        z = jax.random.normal(key, sd.shape, sd.dtype)
        if name in MATRICES:
            return z * jnp.asarray(1.0 / math.sqrt(sd.shape[-2]), sd.dtype)
        if name in ("embed", "lm_head"):
            return z * jnp.asarray(head_scale, sd.dtype)
        if name in BIASES:
            return z * jnp.asarray(0.2, sd.dtype)
        if name in NORMS:
            return 1 + z * jnp.asarray(0.1, sd.dtype)
        raise ValueError(f"no initializer for parameter {name!r}")

    def make(words):
        base = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        leaves = [leaf(jax.random.fold_in(base, i), path, sd)
                  for i, (path, sd) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)


def param_bytes(shapes) -> int:
    return sum(int(np.prod(sd.shape)) * jnp.dtype(sd.dtype).itemsize
               for sd in jax.tree_util.tree_leaves(shapes))
