"""Production mesh construction (see MULTI-POD DRY-RUN spec).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS before any jax import to obtain 512 host devices.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s_ in shape:
        n *= s_
    # the dry-run spawns 512 host devices; the single-pod mesh uses the first
    # 256 of them
    devs = jax.devices()[:n]
    return jax.make_mesh(
        shape, axes, devices=devs, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests (host devices)."""
    n = 1
    for s_ in shape:
        n *= s_
    return jax.make_mesh(
        shape, axes, devices=jax.devices()[:n],
        axis_types=(AxisType.Auto,) * len(axes),
    )


# TPU v5e-class hardware constants used by the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW_PER_LINK = 50e9  # B/s  (~50 GB/s/link)
