"""Tensor-parallel (sharded) serving engine: token-for-token identity
with the single-device engine (docs/sharding.md).

The mesh tests need forced host devices and SKIP on a single-device
backend; CI runs them in the dedicated `host-mesh` job under

    XLA_FLAGS=--xla_force_host_platform_device_count=4

(locally: the same env var in front of pytest). The mesh-1 tests run
everywhere and keep the sharded code path covered by the default tier-1
suite.
"""
import jax
import pytest

from repro.core.decoding import DecodeConfig
from repro.core.grammars import BUILTIN
from repro.serving.engine import Engine, Request

MAX_LEN = 160

needs2 = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=4; CI host-mesh job)")
needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=4; CI host-mesh job)")


@pytest.fixture(scope="module")
def harness(tokenizer, grammar_bundle):
    """One tiny model + every builtin grammar; a single-device baseline
    engine and a factory for mesh engines sharing the same params."""
    from dataclasses import replace

    from repro.configs import get_config
    from repro.models.model import build_model
    bundles = {}
    for name in BUILTIN:
        g, tab, store, _ = grammar_bundle(name)
        bundles[name] = (g, tab, store)
    cfg = get_config("syncode-demo")
    cfg = replace(cfg, vocab_size=tokenizer.vocab_size, num_layers=2,
                  d_model=128, d_ff=256, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    made = {}

    def make(mesh_size=None, **kw):
        key = (mesh_size, tuple(sorted(kw.items())))
        if key not in made:
            mesh = None
            if mesh_size is not None:
                from repro.launch.mesh import make_serving_mesh
                mesh = make_serving_mesh(mesh_size)
            kw.setdefault("slots", 4)
            made[key] = Engine(model, params, tokenizer, bundles,
                               max_len=MAX_LEN, mesh=mesh, **kw)
        return made[key]

    return make, bundles


def _reqs(grammar, n=4, max_new=12, method="greedy", temperature=0.9,
          top_k=None, top_p=None, prompt=b"Q: generate. A:", seed0=0):
    return [Request(rid=i, prompt=prompt, grammar=grammar,
                    max_new_tokens=max_new,
                    decode=DecodeConfig(method=method,
                                        temperature=temperature,
                                        top_k=top_k, top_p=top_p),
                    seed=seed0 + i) for i in range(n)]


def _assert_identical(base_states, mesh_states):
    assert len(base_states) == len(mesh_states)
    for a, b in zip(base_states, mesh_states):
        assert a.req.rid == b.req.rid
        assert a.token_ids == b.token_ids, (a.req.rid, a.generated,
                                            b.generated)
        assert a.finish_reason == b.finish_reason


# --------------------- mesh-1: always-on coverage --------------------------

def test_mesh1_generate_identical(harness):
    """A 1-device mesh exercises the whole sharded path (placements,
    use_sharding contexts, the selector gather) on any backend."""
    make, _ = harness
    base, m1 = make(), make(1)
    for gname in ("json", "calc"):
        bs, _ = base.generate(_reqs(gname, method="sample"))
        ms, stats = m1.generate(_reqs(gname, method="sample"))
        _assert_identical(bs, ms)
        assert stats.mesh_devices == 1


def test_mesh1_store_words_follow_vocab(harness):
    """Under a mesh the packed store keeps ceil(V/32) words, so its word
    shards line up with the logits' vocab shards; only the unsharded
    engine pads it to the fused kernel's (8, 128) tile."""
    make, _ = harness
    base, m1 = make(), make(1)
    V = base.model.cfg.vocab_size
    assert m1._store_cat.shape[1] == -(-V // 32)
    assert base._store_cat.shape[0] % 8 == 0
    assert base._store_cat.shape[1] % 128 == 0


def test_mesh_requires_model_axis(harness):
    make, bundles = harness
    eng = make()
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="model"):
        Engine(eng.model, eng.params, eng.tok, bundles, mesh=mesh)


def test_serving_mesh_validates_device_count():
    from repro.launch.mesh import make_serving_mesh
    with pytest.raises(ValueError):
        make_serving_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError):
        make_serving_mesh(0)


# ------------------ mesh 2 / 4: cross-device determinism -------------------

@needs2
@pytest.mark.parametrize("gname", sorted(BUILTIN))
def test_mesh2_generate_greedy_identical(harness, gname):
    make, _ = harness
    bs, _ = make().generate(_reqs(gname))
    ms, stats = make(2).generate(_reqs(gname))
    _assert_identical(bs, ms)
    assert stats.mesh_devices == 2


@needs4
@pytest.mark.parametrize("gname", sorted(BUILTIN))
def test_mesh4_generate_greedy_identical(harness, gname):
    make, _ = harness
    bs, _ = make().generate(_reqs(gname))
    ms, stats = make(4).generate(_reqs(gname))
    _assert_identical(bs, ms)
    assert stats.mesh_devices == 4


@needs2
@pytest.mark.parametrize("gname", sorted(BUILTIN))
def test_mesh2_generate_sampled_identical(harness, gname):
    """Sampled decoding: per-slot PRNG streams + the selector's single
    gather must reproduce the single-device draw exactly."""
    make, _ = harness
    reqs = lambda: _reqs(gname, method="sample", temperature=0.9,
                         top_k=40, top_p=0.95)
    bs, _ = make().generate(reqs())
    ms, _ = make(2).generate(reqs())
    _assert_identical(bs, ms)


@needs4
@pytest.mark.parametrize("gname", sorted(BUILTIN))
def test_mesh4_generate_sampled_identical(harness, gname):
    make, _ = harness
    reqs = lambda: _reqs(gname, method="sample", temperature=1.1)
    bs, _ = make().generate(reqs())
    ms, _ = make(4).generate(reqs())
    _assert_identical(bs, ms)


@needs2
@pytest.mark.parametrize("gname", sorted(BUILTIN))
def test_mesh2_speculative_greedy_identical(harness, gname):
    """Greedy speculative decoding (jump-forward + draft-verify spans)
    through the vocab-sharded mask/select path."""
    make, _ = harness
    bs, _ = make().generate_speculative(_reqs(gname))
    ms, _ = make(2).generate_speculative(_reqs(gname))
    _assert_identical(bs, ms)


@needs4
@pytest.mark.parametrize("gname", sorted(BUILTIN))
def test_mesh4_speculative_greedy_identical(harness, gname):
    make, _ = harness
    bs, _ = make().generate_speculative(_reqs(gname))
    ms, _ = make(4).generate_speculative(_reqs(gname))
    _assert_identical(bs, ms)


@needs2
def test_mesh2_paged_identical(harness):
    """Paged KV serving under the mesh: replicated page pools +
    vocab-sharded mask path, same tokens as the unsharded dense
    engine."""
    make, _ = harness
    bs, _ = make().generate(_reqs("json", method="sample"))
    ms, stats = make(2, paged=True, page_size=8).generate(
        _reqs("json", method="sample"))
    _assert_identical(bs, ms)
    assert stats.kv_peak_utilization > 0


@needs2
def test_mesh2_mixed_grammars_one_pool(harness):
    """Different grammars in one decode pool index one vocab-sharded
    concatenated store via per-slot row offsets."""
    make, _ = harness
    reqs = []
    for i, gname in enumerate(sorted(BUILTIN)):
        reqs.append(Request(rid=i, prompt=b"Q:", grammar=gname,
                            max_new_tokens=10,
                            decode=DecodeConfig(method="sample",
                                                temperature=0.9),
                            seed=i))
    bs, _ = make().generate(list(reqs))
    ms, _ = make(2).generate(list(reqs))
    _assert_identical(bs, ms)


@needs2
def test_mesh2_store_is_sharded(harness):
    """The packed store actually lives vocab-sharded on the mesh (not
    silently replicated): its sharding splits the word axis."""
    make, _ = harness
    eng = make(2)
    sh = eng._store_cat.sharding
    spec = sh.spec
    assert spec[1] == "model", spec
    assert eng.params["embed_block"]["embed"].sharding.spec[0] == "model"


# --------------------- async engine on the mesh ----------------------------
# The AsyncEngine drives the same StepLoop as the sync entry points, so
# mesh identity must survive the async front-end too (the CI host-mesh
# job runs these alongside the sync determinism tests above).

def _async_generate(engine, reqs):
    import asyncio

    from repro.serving.async_engine import AsyncEngine

    async def go():
        aeng = AsyncEngine(engine)
        try:
            return await aeng.generate(reqs)
        finally:
            await aeng.drain()
    return asyncio.run(go())


def test_mesh1_async_generate_identical(harness):
    """Always-on: async + overlap on a 1-device mesh matches the
    unsharded sync baseline token-for-token."""
    make, _ = harness
    bs, _ = make().generate(_reqs("json", method="sample"))
    ms, stats = _async_generate(make(1), _reqs("json", method="sample"))
    _assert_identical(bs, ms)
    assert stats.mesh_devices == 1


@needs2
@pytest.mark.parametrize("gname", sorted(BUILTIN))
def test_mesh2_async_generate_identical(harness, gname):
    make, _ = harness
    bs, _ = make().generate(_reqs(gname, method="sample",
                                  temperature=1.0))
    ms, stats = _async_generate(make(2), _reqs(gname, method="sample",
                                               temperature=1.0))
    _assert_identical(bs, ms)
    assert stats.mesh_devices == 2


@needs2
def test_mesh2_async_paged_identical(harness):
    make, _ = harness
    bs, _ = make().generate(_reqs("json", n=5, max_new=10))
    ms, _ = _async_generate(make(2, paged=True, page_size=8),
                            _reqs("json", n=5, max_new=10))
    _assert_identical(bs, ms)


@needs2
def test_mesh2_async_speculative_identical(harness):
    import asyncio

    from repro.serving.async_engine import AsyncEngine
    from repro.spec import SpecConfig
    make, _ = harness
    bs, _ = make().generate_speculative(_reqs("jsonmsg"),
                                        spec=SpecConfig())

    async def go():
        aeng = AsyncEngine(make(2), spec=SpecConfig())
        try:
            return await aeng.generate(_reqs("jsonmsg"))
        finally:
            await aeng.drain()
    ms, _ = asyncio.run(go())
    _assert_identical(bs, ms)
