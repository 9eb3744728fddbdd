"""Pallas TPU kernel: fused hook/shortcut connected-components rounds.

Hardware adaptation (DESIGN.md §2.9): the jnp oracle issues one XLA
gather/scatter pair — a full HBM round trip for the label vector and the ELL
neighbour blocks — per hook/shortcut round.  This kernel keeps the labels
*and* both neighbour blocks VMEM-resident across ``rounds`` consecutive
rounds: one ``pallas_call`` loads ``cols`` (out-neighbours), ``colsT``
(in-neighbours, the ELL transpose built once by ``ops.py``) and the label
row, then runs a ``fori_loop`` of fused rounds entirely in VMEM before
writing the labels (plus a changed flag) back once.

The scatter-min of the oracle's push step is re-expressed as a gather-min
over the *transposed* adjacency — ``min`` over the identical edge set, so the
kernel is bit-for-bit identical to ``ref.py`` (the parity contract of the
``cc_labels`` op).

Gather layout.  The TPU compiler gathers only inside one ``(8, 128)`` vreg,
so ``table[idx]`` over n labels is assembled from in-vreg lane gathers: the
label table sits as ``(n/128, 128)`` rows, each neighbour slot's indices as
a matching ``(n/128, 128)`` plane, and for every 8-row index tile the kernel
walks the table rows that tile actually references (its min..max row),
broadcasting each row to a vreg, lane-gathering it by ``idx % 128`` and
keeping the lanes whose ``idx // 128`` is that row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.backend import resolve_interpret

_BIG = 2**30  # plain python int: Pallas kernels cannot capture traced consts
LANES = 128
TILE = 8 * LANES  # labels per (8, 128) index tile


def _gather_min_into(out_ref, table_ref, idx_planes):
    """``out = min(out, min_q table[idx_q])`` over the index planes
    ``idx_planes`` (a list of ``(rows, 128)`` refs or ref views); ``-1``
    indices contribute nothing."""
    n_tiles = out_ref.shape[0] // 8

    def tile(t, _):
        rows = pl.ds(pl.multiple_of(t * 8, 8), 8)
        acc = out_ref[rows, :]
        for plane in idx_planes:
            idx = plane[rows, :]
            hi = idx >> 7
            lane = idx & (LANES - 1)
            lo_row = jnp.min(jnp.where(idx >= 0, hi, _BIG))
            hi_row = jnp.max(hi)

            def row(r, a, hi=hi, lane=lane):
                tab = jnp.broadcast_to(table_ref[pl.ds(r, 1), :], (8, LANES))
                g = jnp.take_along_axis(tab, lane, axis=1)
                return jnp.minimum(a, jnp.where(hi == r, g, _BIG))

            acc = jax.lax.fori_loop(lo_row, hi_row + 1, row, acc)
        out_ref[rows, :] = acc
        return 0

    jax.lax.fori_loop(0, n_tiles, tile, 0)


def _cc_rounds_kernel(
    oc_ref, ic_ref, lab_ref, out_ref, chg_ref, l1_ref, l2_ref, *,
    rounds: int,
):
    k_out, k_in = oc_ref.shape[0], ic_ref.shape[0]
    out_ref[...] = lab_ref[...]

    def rd(_, chg):
        l0 = out_ref[...]
        # hook: pull the min label over out-neighbours...
        l1_ref[...] = l0
        _gather_min_into(l1_ref, out_ref, [oc_ref.at[q] for q in range(k_out)])
        # ...then over in-neighbours (== the oracle's scatter-min push)
        l2_ref[...] = l1_ref[...]
        _gather_min_into(l2_ref, l1_ref, [ic_ref.at[q] for q in range(k_in)])
        # shortcut: jump to the label's label
        out_ref[...] = jnp.full(out_ref.shape, _BIG, jnp.int32)
        _gather_min_into(out_ref, l2_ref, [l2_ref])
        return jnp.maximum(chg, jnp.max((out_ref[...] != l0).astype(jnp.int32)))

    chg = jax.lax.fori_loop(0, rounds, rd, jnp.int32(0))
    chg_ref[...] = jnp.full(chg_ref.shape, chg, jnp.int32)


@functools.partial(jax.jit, static_argnames=("rounds", "interpret"))
def cc_rounds_pallas(
    oc_planes: jnp.ndarray,
    ic_planes: jnp.ndarray,
    labels: jnp.ndarray,
    *,
    rounds: int,
    interpret: bool | str = "auto",
):
    """Run ``rounds`` fused hook/shortcut rounds in one VMEM-resident call.

    Args:
      oc_planes: ``(k_out, R, 128)`` int32 out-neighbour slot planes (slot q
        of vertex ``128·r + l`` at ``[q, r, l]``, ``-1`` = empty); ``R`` is
        a multiple of 8.
      ic_planes: ``(k_in, R, 128)`` int32 in-neighbour slot planes (the
        transpose of ``oc_planes``; see ``ops.transpose_ell``).
      labels: ``(R, 128)`` int32 current labels.
      rounds: fused rounds per call (static).

    Returns:
      ``(labels', changed)`` with ``labels'`` ``(R, 128)`` int32 and
      ``changed`` ``(8, 128)`` int32 — nonzero iff any round changed any
      label.
    """
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(_cc_rounds_kernel, rounds=rounds)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    resident = 4 * (oc_planes.size + ic_planes.size + 4 * labels.size)
    # the scope names the kernel's device time in the profiler trace
    with jax.named_scope("cc_labels"):
        return pl.pallas_call(
            kernel,
            in_specs=[vmem, vmem, vmem],
            out_specs=[vmem, vmem],
            out_shape=[
                jax.ShapeDtypeStruct(labels.shape, jnp.int32),
                jax.ShapeDtypeStruct((8, LANES), jnp.int32),
            ],
            scratch_shapes=[pltpu.VMEM(labels.shape, jnp.int32)] * 2,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=max(resident + (4 << 20), 16 << 20)
            ),
            interpret=interpret,
            name="cc_labels",
        )(oc_planes, ic_planes, labels)
