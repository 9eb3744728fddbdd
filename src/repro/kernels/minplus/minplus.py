"""Pallas TPU kernel: blocked dense min-plus matmul with orientation combos.

Hardware adaptation (DESIGN.md §2): min-plus is not a (+,×) ring, so the MXU
is unusable — the kernel instead tiles (BM, BK)·(BK, BN) panels into VMEM and
reduces k with VPU broadcast-add + min, accumulating the output block across
the k grid dimension in-place (the revisited-output accumulation pattern).
The orientation contraction (min over the middle strand c) rides along as two
extra lanes.

Layout: the four orientation components travel as separate planes —
``a`` as ``(4, M, K)``, ``b`` as ``(4, K, N)`` — so every operand the VPU
touches is a plain 2-D (sublane, lane) tile.  Step k of the reduction needs
column k of each ``a`` plane (dynamic lane rotate, then lane 0) and row k of
each ``b`` plane (dynamic sublane slice); both lower to plain vector ops.
Block shapes default to (128, 128, 128), tile-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.backend import resolve_interpret

INF = float("inf")  # plain python float: Pallas kernels cannot capture traced consts


def _minplus_kernel(a_ref, b_ref, o_ref):
    bm, bk = a_ref.shape[1], a_ref.shape[2]
    bn = b_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, INF, jnp.float32)

    def body(k, acc):
        # a[:, k, 2x+c] as (BM, 1) columns, b[k, :, 2c+y] as (1, BN) rows
        acol = [
            pltpu.roll(a_ref[p], (bk - k) % bk, 1)[:, 0:1] for p in range(4)
        ]
        brow = [b_ref[p, pl.ds(k, 1), :] for p in range(4)]
        out = []
        for x in range(2):
            for y in range(2):
                s0 = acol[2 * x] + brow[y]  # c = 0
                s1 = acol[2 * x + 1] + brow[2 + y]  # c = 1
                out.append(jnp.minimum(acc[2 * x + y], jnp.minimum(s0, s1)))
        return tuple(out)

    acc0 = tuple(jnp.full((bm, bn), INF, jnp.float32) for _ in range(4))
    acc = jax.lax.fori_loop(0, bk, body, acc0)
    for p in range(4):
        o_ref[p] = jnp.minimum(o_ref[p], acc[p])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def minplus_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | str = "auto",
) -> jnp.ndarray:
    """a (M, K, 4), b (K, N, 4) -> (M, N, 4) f32.

    ``interpret="auto"`` compiles on TPU and interprets elsewhere."""
    interpret = resolve_interpret(interpret)
    m, k, _ = a.shape
    n = b.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    pm, pn, pk = -(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk
    ap = jnp.pad(jnp.moveaxis(a.astype(jnp.float32), 2, 0),
                 ((0, 0), (0, pm - m), (0, pk - k)), constant_values=INF)
    bp = jnp.pad(jnp.moveaxis(b.astype(jnp.float32), 2, 0),
                 ((0, 0), (0, pk - k), (0, pn - n)), constant_values=INF)
    grid = (pm // bm, pn // bn, pk // bk)
    # the scope names the kernel's device time in the profiler trace
    with jax.named_scope("minplus_dense"):
        out = pl.pallas_call(
            _minplus_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((4, bm, bk), lambda i, j, kk: (0, i, kk)),
                pl.BlockSpec((4, bk, bn), lambda i, j, kk: (0, kk, j)),
            ],
            out_specs=pl.BlockSpec((4, bm, bn), lambda i, j, kk: (0, i, j)),
            out_shape=jax.ShapeDtypeStruct((4, pm, pn), jnp.float32),
            interpret=interpret,
            name="minplus_dense",
        )(ap, bp)
    return jnp.moveaxis(out[:, :m, :n], 0, 2)
