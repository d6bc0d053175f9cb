"""Sharding rules, HLO cost analyzer, and a real (small-mesh) dry-run in
a subprocess with forced host devices."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P


def _fake_mesh():
    # single-device "mesh" with the production axis names for rule tests
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


class _MeshShape:
    """Duck-typed mesh exposing .shape and .axis_names for rule tests."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_param_spec_rules():
    from repro.distributed.sharding import param_spec
    mesh = _MeshShape({"data": 16, "model": 16})
    assert param_spec("['embed_block']['embed']", (163840, 7168), mesh) \
        == P("model", None)
    assert param_spec("['groups'][0][0]['attn']['wq']", (61, 7168, 8192),
                      mesh) == P(None, None, "model")
    assert param_spec("['groups'][0][0]['attn']['wo']", (61, 8192, 7168),
                      mesh) == P(None, "model", None)
    assert param_spec("['groups'][0][0]['moe']['w_gate']",
                      (60, 384, 7168, 2048), mesh) == \
        P(None, "model", None, None)
    # non-divisible head dim -> replicated (smollm: 15 heads)
    assert param_spec("['groups'][0][0]['attn']['wq']", (32, 960, 960),
                      mesh) == P(None, None, "model")
    assert param_spec("['groups'][0][0]['attn']['wq']", (32, 960, 900),
                      mesh) == P(None, None, None)
    # fsdp adds a data axis on the largest free divisible dim
    assert param_spec("['groups'][0][0]['attn']['wq']", (61, 7168, 8192),
                      mesh, fsdp=True) == P(None, "data", "model")


def test_hlo_cost_scan_trip_scaling():
    from repro.distributed.hlo_cost import roofline_counts

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jnp.ones((256, 256))
    comp = jax.jit(f).lower(x, x).compile()
    rc = roofline_counts(comp.as_text())
    expect = 7 * 2 * 256 ** 3
    assert abs(rc["flops"] - expect) / expect < 0.05, rc["flops"]


def test_collective_accounting_ring_factors():
    from repro.distributed.hlo_stats import collective_stats
    fake = """
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3}}
  %ag = f32[4096]{0} all-gather(%y), replica_groups={{0,1,2,3}}
"""
    st = collective_stats(fake)
    assert st["all-reduce"]["count"] == 1
    assert st["all-reduce"]["wire_bytes"] == int(2 * 3 / 4 * 4096)
    assert st["all-gather"]["wire_bytes"] == int(3 / 4 * 16384)


DRYRUN_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, __SRC__)
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.model import build_model
from repro.distributed.api import use_sharding
from repro.distributed.sharding import (activation_rules, batch_shardings,
                                        cache_shardings, params_shardings)
from repro.launch.mesh import make_local_mesh
from repro.launch.shapes import batch_specs

cfg = get_config(__ARCH__).reduced(vocab_size=512, d_model=256)
model = build_model(cfg)
mesh = make_local_mesh(4)
params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
p_sh = params_shardings(params, mesh)
rules = activation_rules(mesh, cfg, 4)
bspec = batch_specs(cfg, 64, 4, with_labels=True)
b_sh = batch_shardings(bspec, mesh)

def loss(p, b):
    return model.loss(p, b)[0]

with use_sharding(mesh, rules):
    lowered = jax.jit(loss, in_shardings=(p_sh, b_sh)).lower(params, bspec)
compiled = lowered.compile()
assert compiled.memory_analysis() is not None
caches = jax.eval_shape(lambda: model.init_decode_caches(4, 64))
c_sh = cache_shardings(caches, mesh, cfg)
tok = jax.ShapeDtypeStruct((4,), jax.numpy.int32)
t_sh = batch_shardings(dict(t=tok), mesh)["t"]
with use_sharding(mesh, rules):
    dec = jax.jit(model.decode_step,
                  in_shardings=(p_sh, c_sh, t_sh, t_sh)
                  ).lower(params, caches, tok, tok)
dec.compile()
print("MESH_DRYRUN_OK")
"""


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen3-moe-30b-a3b",
                                  "mamba2-370m", "recurrentgemma-9b"])
def test_sharded_lower_compile_8dev(arch):
    """Reduced configs must lower+compile train loss AND decode on a real
    (8 placeholder device) mesh — the mini version of the production
    dry-run, runnable inside the test suite."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = DRYRUN_SNIPPET.replace("__SRC__", repr(os.path.abspath(src))) \
        .replace("__ARCH__", repr(arch))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH_DRYRUN_OK" in r.stdout
