"""Production mesh definition (assignment: 16x16 single pod = 256 chips,
2x16x16 multi-pod = 512 chips). A function, not a module-level constant,
so importing never touches jax device state.

Every mesh here has Auto axes: the sharding rules place arrays with
NamedSharding and let GSPMD propagate, which jax's Explicit default for
`jax.make_mesh` would reject."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (tests / CPU demos)."""
    n = jax.device_count()
    data = n // model_axis
    return _auto_mesh((data, model_axis), ("data", "model"))


def make_serving_mesh(model_parallel: int = 1):
    """Serving-engine mesh: a pure "model" axis of `model_parallel`
    devices (data axis 1 — the engine's continuous-batching pool IS the
    batch dim and stays host-driven). Works on real accelerators and on
    forced host devices alike (CPU CI runs under
    XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    m = int(model_parallel)
    if m < 1:
        raise ValueError(f"model_parallel must be >= 1, got {m}")
    if m > jax.device_count():
        raise ValueError(
            f"serving mesh wants {m} devices but only "
            f"{jax.device_count()} exist (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={m} for CPU runs)")
    return _auto_mesh((1, m), ("data", "model"))


# TPU v5e hardware constants, per chip (roofline targets; Google Cloud
# documentation, "TPU v5e")
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per direction)
HBM_BYTES = 16e9                # v5e HBM capacity per chip
