"""Compile the Pallas kernels for a v5e chip without one (no run).

The TPU compiler ships with jaxlib and compiles for a *described* chip, so
every kernel of the pipeline is lowered by Mosaic here at the widths
``chip_smoke.py`` drives on the chip: what Mosaic refuses (untiled blocks,
in-kernel gathers it cannot lower, too much VMEM) fails in CI instead of
on the chip.  Only shapes are passed; nothing executes.

The topology is described inside a module-scoped fixture — never while a
module is imported — because only one process at a time may load the TPU
library: under pytest-xdist only the worker that runs this file does.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

# E. coli-size run of chip_smoke.py: 46k reads of ≤ 2560 bp, 4096-pair
# alignment chunks, band 65 × 4096 steps, pileup bands of 512 columns,
# 2·n-vertex state graphs, dense TR squares up to TR_DENSE_MAX_ROWS.
READ_WIDTH = 2560
ALIGN_CHUNK = 4096
N_STATES = 2 * 46_080


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _sds(one_chip):
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return sds


def test_xdrop_compiles_for_v5e(one_chip, no_cache):
    from repro.kernels.xdrop.xdrop import xdrop_pallas

    s = _sds(one_chip)
    seq = s((ALIGN_CHUNK, READ_WIDTH), jnp.uint8)
    vec = s((ALIGN_CHUNK,))
    compiled = _compile(
        lambda *a: xdrop_pallas(*a, band=65, max_steps=4096, xdrop=30,
                                pairs_per_block=128, interpret=False),
        seq, vec, vec, vec, seq, vec, vec, vec,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_pileup_compiles_for_v5e(one_chip, no_cache):
    from repro.kernels.pileup.pileup import pileup_pallas

    s = _sds(one_chip)
    c, l, m = 8, 1 << 20, 8192  # two SMEM chunks of piece starts
    compiled = _compile(
        lambda *a: pileup_pallas(*a, min_depth=2, band=512,
                                 interpret=False),
        s((c, l), jnp.uint8), s((c, m, READ_WIDTH), jnp.uint8), s((c, m)),
        s((c, m)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_cc_compiles_for_v5e(one_chip, no_cache):
    from repro.kernels.cc.cc import LANES, TILE, cc_rounds_pallas

    s = _sds(one_chip)
    rows = -(-N_STATES // TILE) * TILE // LANES
    compiled = _compile(
        lambda a, b, lab: cc_rounds_pallas(a, b, lab, rounds=8,
                                           interpret=False),
        s((8, rows, LANES)), s((8, rows, LANES)), s((rows, LANES)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_minplus_compiles_for_v5e(one_chip, no_cache):
    from repro.core.transitive_reduction import TR_DENSE_MAX_ROWS
    from repro.kernels.minplus.minplus import minplus_pallas

    s = _sds(one_chip)
    n = TR_DENSE_MAX_ROWS
    compiled = _compile(
        lambda a, b: minplus_pallas(a, b, interpret=False),
        s((n, n, 4), jnp.float32), s((n, n, 4), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def _spgemm_stage_shapes(s):
    # 100 kb four-chip run of chip_smoke.py: 2×2 grid, two ring stages per
    # call, 500 reads and 2^16 k-mer rows per device
    stages, n, nb, ka, kb = 2, 512, 1 << 16, 160, 56
    return (
        s((stages,)), s((stages, n, ka)), {"pos": s((stages, n, ka))},
        s((stages, nb, kb)), {"pos": s((stages, nb, kb))},
    )


def test_spgemm_ring_stages_tpu_path_compiles_for_v5e(one_chip, no_cache):
    """The fused stage kernel has no TPU lowering (it sorts in-kernel), so
    compiled on a TPU the registered op runs its oracle — an explicit
    choice (``NO_TPU_LOWERING``), recorded in ``op_impls``.  That path
    compiles at chip widths; and the raw kernel is still refused by the
    compiler, so the recorded choice stays true (when it lowers, switch
    the op to it and turn this into a ``tpu_custom_call`` check)."""
    from jax._src.pallas.mosaic.error_handling import MosaicError

    from repro.core.semiring import overlap_semiring
    from repro.kernels.spgemm.ops import (
        kernel_runs,
        spgemm_ring_stages_pallas,
    )
    from repro.kernels.spgemm.spgemm import (
        spgemm_ring_stages_pallas as fused_kernel,
    )

    assert not kernel_runs(interpret=False)
    s = _sds(one_chip)
    shapes = _spgemm_stage_shapes(s)
    kw = dict(semiring=overlap_semiring, capacity=64, n_cols_out=46_080)
    compiled = _compile(
        lambda *a: spgemm_ring_stages_pallas(*a, interpret=False, **kw),
        *shapes,
    )
    assert "tpu_custom_call" not in compiled.as_text()
    with pytest.raises((MosaicError, NotImplementedError, ValueError)):
        _compile(lambda *a: fused_kernel(*a, interpret=False, **kw), *shapes)


def _small_kernel_call(kernel, s):
    """``(fn, shapes)`` of one Pallas kernel at a small v5e-legal size."""
    if kernel == "xdrop_extend":
        from repro.kernels.xdrop.xdrop import xdrop_pallas

        seq, vec = s((128, 256), jnp.uint8), s((128,))
        return (lambda *a: xdrop_pallas(*a, band=33, max_steps=256,
                                        interpret=False),
                (seq, vec, vec, vec, seq, vec, vec, vec))
    if kernel == "pileup_vote":
        from repro.kernels.pileup.pileup import pileup_pallas

        c, m = 8, 4096  # one SMEM chunk of piece starts per contig
        return (lambda *a: pileup_pallas(*a, min_depth=2, band=512,
                                         interpret=False),
                (s((c, 4096), jnp.uint8), s((c, m, 256), jnp.uint8),
                 s((c, m)), s((c, m))))
    if kernel == "cc_labels":
        from repro.kernels.cc.cc import LANES, cc_rounds_pallas

        return (lambda a, b, lab: cc_rounds_pallas(a, b, lab, rounds=2,
                                                   interpret=False),
                (s((4, 8, LANES)), s((4, 8, LANES)), s((8, LANES))))
    from repro.kernels.minplus.minplus import minplus_pallas

    return (lambda a, b: minplus_pallas(a, b, interpret=False),
            (s((256, 256, 4), jnp.float32), s((256, 256, 4), jnp.float32)))


@pytest.mark.parametrize("kernel", ["xdrop_extend", "pileup_vote",
                                    "cc_labels", "minplus_dense"])
def test_kernel_custom_call_carries_its_name(kernel, one_chip, no_cache):
    """Each Mosaic custom call is named for its kernel (``pallas_call``'s
    ``name=``) and sits under a named scope of the same name, so the
    profiler's device trace finds the kernel by name."""
    fn, shapes = _small_kernel_call(kernel, _sds(one_chip))
    text = _compile(fn, *shapes).as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        assert re.match(rf"\s*(ROOT )?%{kernel}[.\d]* = ", ln), ln[:120]
        assert f"/{kernel}/" in ln, ln[:120]
