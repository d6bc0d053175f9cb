"""The configuration files: stated source and cuts, and parameter bytes
that match the model the program builds from them (shapes only, through
jax.eval_shape: nothing is allocated)."""
import json

import jax
import pytest

import tiny
from harness import counts, weights

FILES = sorted((tiny.HERE / "configs").glob("*.json"))


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_config_states_its_cut(path):
    cfg = json.loads(path.read_text())
    assert cfg["name"] == path.stem
    assert cfg["source"].startswith("https://")
    assert isinstance(cfg["reduced"], list)
    for key in cfg["reduced"]:
        assert key in cfg and key in cfg["published"]
        assert cfg[key] != cfg["published"][key]
    assert cfg["assumed"] and cfg["deployment"]
    s = cfg["serve"]
    assert (s["num_layers"], s["d_model"], s["d_ff"], s["vocab_size"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["intermediate_size"], cfg["vocab_size"])
    assert (s["num_heads"], s["num_kv_heads"]) == (
        cfg["num_attention_heads"], cfg["num_key_value_heads"])
    assert s["tie_embeddings"] == cfg["tie_word_embeddings"]
    assert s["head_dim"] * s["num_heads"] == cfg["hidden_size"]


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_param_bytes_match_the_built_model(path):
    from repro.models.config import ModelConfig
    from repro.models.model import build_model
    cfg = json.loads(path.read_text())
    model = build_model(ModelConfig(**cfg["serve"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == cfg["param_count"]
    assert weights.param_bytes(shapes) == cfg["param_bytes"] == 2 * n
    # the matmul weights a token multiplies by, by hand
    s = cfg["serve"]
    tied_head = s["d_model"] * s["vocab_size"]
    norms_and_biases = n - counts.matmul_params(s) - (
        0 if s["tie_embeddings"] else tied_head)
    assert 0 < norms_and_biases < 0.001 * n


def test_weights_follow_the_seed_not_the_program():
    """Same seed, same weights; another seed, other weights; every leaf
    in the served dtype."""
    from repro.models.config import ModelConfig
    from repro.models.model import build_model
    cfg = tiny.spec()["config"]["serve"]
    model = build_model(ModelConfig(**cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    make = weights.maker(shapes, cfg["d_model"])
    a = make(weights.seed_words(2**33 + 1))
    b = make(weights.seed_words(2**33 + 1))
    c = make(weights.seed_words(1))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert any((x != y).any() for x, y in zip(la, lc))
    assert all(x.dtype == s.dtype for x, s in
               zip(la, jax.tree_util.tree_leaves(shapes)))
