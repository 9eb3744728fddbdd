"""Pallas TPU kernel: banded pileup accumulation + majority vote (consensus).

Hardware adaptation (DESIGN.md §2.8): the full base-count pileup tensor
``(n_contigs, max_len, 4)`` would be the largest array in the pipeline, so it
is never materialized in HBM — the grid tiles it as (contig, column-band)
blocks and each program accumulates four ``(1, band)`` int32 count rows in
VMEM/VREGs by looping over the contig's pieces (fixed trip count M, the
chain-capacity padding of ``ContigSet``, walked in SMEM-sized chunks along a
third grid axis).  Pieces that miss the band are
skipped; each piece that meets it is fetched from HBM as one tile-aligned
DMA window around the band, lane-rotated onto the band's columns (one
dynamic rotate, then static ones for the ±``COH_WIN`` coherence halo), and
voted.  The vote epilogue (argmax + strict-majority + min-depth gating) runs
on the block before only the three ``(band,)`` result rows are written back.

Counts are integers and the tie-break is first-max-wins, so the kernel is
bit-for-bit identical to the jnp oracle in ``ref.py`` — the parity contract
of the ``consensus`` op (DESIGN.md §2.5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.backend import resolve_interpret
from .ref import COH_DEN, COH_MIN_VALID, COH_NUM, COH_WIN

LANES = 128
ROWS = 8  # DMA row granularity of a uint8 HBM array (one tile of rows)
PIECE_CHUNK = 4096  # piece starts/lengths held in SMEM per grid step


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _fetch_row(hbm, buf, sem, row, a0):
    """Row ``row`` of the uint8 HBM array, lanes ``[a0, a0 + width)``, as an
    int32 ``(1, width)`` value (``a0`` a multiple of 128).  The DMA moves the
    tile-aligned group of ``ROWS`` rows; the wanted row is picked on-chip."""
    g = pl.multiple_of((row // ROWS) * ROWS, ROWS)
    cp = pltpu.make_async_copy(
        hbm.at[pl.ds(g, ROWS), pl.ds(pl.multiple_of(a0, LANES), buf.shape[1])],
        buf, sem,
    )
    cp.start()
    cp.wait()
    x = buf[...].astype(jnp.int32)
    sel = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) == row - g
    return jnp.sum(jnp.where(sel, x, 0), axis=0, keepdims=True)


def _shift(x, k: int, width: int):
    """``x[:, k : k + width]`` for a static ``k`` (a lane rotate + aligned
    prefix)."""
    if k:
        x = pltpu.roll(x, x.shape[1] - k, 1)
    return x[:, :width]


def _pileup_kernel(
    start_ref, plen_ref, draft_hbm, pieces_hbm, pol_ref, dep_ref, agr_ref,
    cnt_ref, dbuf, pbuf, sem, *, band: int, min_depth: int, l_full: int,
    m: int, pad_l: int,
):
    # l_full is the UNPADDED column count: votes and coherence comparisons
    # beyond it are invalid (bit-parity with the oracle, which never sees
    # the band-multiple padding).  Grid axis 2 walks the contig's pieces in
    # chunks of start_ref.shape[0], accumulating into cnt_ref.
    c = pl.program_id(0)
    col0 = pl.program_id(1) * band
    chunk = pl.program_id(2)
    mc = start_ref.shape[0]
    q = jax.lax.broadcasted_iota(jnp.int32, (1, band), 1)
    cols = col0 + q
    # draft window [col0 − 128, col0 + band + 128) (the draft rows carry 128
    # columns of left padding), so halo column col0 + q + w sits at q+w+128
    dwin = _fetch_row(draft_hbm, dbuf, sem, c, col0)
    dv = {w: _shift(dwin, LANES + w, band) for w in range(-COH_WIN, COH_WIN + 1)}
    cb_ok = {
        w: (cols + w >= 0) & (cols + w < l_full)
        for w in range(-COH_WIN, COH_WIN + 1)
    }

    def piece(t, counts):
        s = start_ref[t]
        ln = plen_ref[t]
        off = col0 - s  # piece index of the band's first column
        # piece rows carry pad_l columns of left padding; fetch the aligned
        # window whose first COH_WIN + 1 lanes precede off − COH_WIN
        lo = off - COH_WIN + pad_l
        a0 = (lo // LANES) * LANES
        win = _fetch_row(pieces_hbm, pbuf, sem, c * m + chunk * mc + t, a0)
        win = pltpu.roll(win, (win.shape[1] - (lo - a0)) % win.shape[1], 1)
        # win[:, k] now holds piece index off − COH_WIN + k
        idx = off + q
        base = _shift(win, COH_WIN, band)
        ok = (idx >= 0) & (idx < ln) & (cols < l_full)
        # coherence gate (see ref.py): the read must locally agree with the
        # draft around the voted column, else the vote abstains
        match = jnp.zeros((1, band), jnp.int32)
        valid = jnp.zeros((1, band), jnp.int32)
        for w in range(-COH_WIN, COH_WIN + 1):
            if w == 0:
                continue
            rb = idx + w
            v = (rb >= 0) & (rb < ln) & cb_ok[w]
            rv = _shift(win, COH_WIN + w, band)
            match = match + (v & (rv == dv[w])).astype(jnp.int32)
            valid = valid + v.astype(jnp.int32)
        ok &= (COH_DEN * match >= COH_NUM * valid) & (valid >= COH_MIN_VALID)
        return tuple(
            cnt + ((base == b) & ok).astype(jnp.int32)
            for b, cnt in enumerate(counts)
        )

    def body(t, counts):
        s = start_ref[t]
        ln = plen_ref[t]
        meets = (ln > 0) & (s < col0 + band) & (s + ln > col0)
        return jax.lax.cond(meets, lambda x: piece(t, x), lambda x: x, counts)

    @pl.when(chunk == 0)
    def _():
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.int32)

    counts = jax.lax.fori_loop(
        0, mc, body, tuple(cnt_ref[b : b + 1, :] for b in range(4))
    )
    for b in range(4):
        cnt_ref[b : b + 1, :] = counts[b]

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        _vote(counts, dv[0], pol_ref, dep_ref, agr_ref, min_depth=min_depth)


def _vote(counts, draft, pol_ref, dep_ref, agr_ref, *, min_depth: int):
    """Vote epilogue — 4 base rows, unrolled first-max-wins (== argmax
    tie-break of the oracle)."""
    zero = jnp.zeros_like(draft)
    dep = counts[0] + counts[1] + counts[2] + counts[3]
    best = counts[0]
    winner = zero
    for b in range(1, 4):
        better = counts[b] > best
        best = jnp.where(better, counts[b], best)
        winner = jnp.where(better, b, winner)
    change = (dep >= min_depth) & (2 * best > dep)
    pol = jnp.where(change, winner, draft)
    agree = zero
    for b in range(4):
        agree = jnp.where(pol == b, counts[b], agree)
    pol_ref[...] = pol
    dep_ref[...] = dep
    agr_ref[...] = agree


@functools.partial(
    jax.jit, static_argnames=("min_depth", "band", "interpret")
)
def pileup_pallas(
    draft, pieces, start, plen, *, min_depth: int = 2, band: int = 512,
    interpret: bool | str = "auto",
):
    """draft (C, L) uint8, pieces (C, M, LR) uint8, start/plen (C, M) int32
    -> (polished (C, L) uint8, depth (C, L) i32, agree (C, L) i32).

    ``band`` is rounded up to a multiple of 128 lanes.  ``interpret="auto"``
    compiles on TPU and interprets elsewhere."""
    interpret = resolve_interpret(interpret)
    c, l = draft.shape
    m, lr = pieces.shape[1], pieces.shape[2]
    b = _round_up(min(band, l), LANES)
    lp = _round_up(l, b)
    # draft rows: 128 columns of padding each side of the band grid, so the
    # window [col0 − 128, col0 + b + 128) of every band is in range
    draft_p = jnp.pad(draft, ((0, _round_up(c, ROWS) - c),
                              (LANES, lp - l + LANES)))
    # piece rows: pad_l columns of left padding; a piece meeting the band has
    # off ∈ (−b, LR), so its window [a0, a0 + b + 256) stays in range
    pad_l = b + LANES
    width = _round_up(pad_l + lr + b + 2 * LANES, LANES)
    mc = min(m, PIECE_CHUNK)
    mp = _round_up(m, mc)  # padding pieces have plen 0 and are skipped
    pieces_p = jnp.pad(
        pieces, ((0, 0), (0, mp - m), (pad_l, width - pad_l - lr))
    ).reshape(c * mp, width)
    pieces_p = jnp.pad(pieces_p, ((0, _round_up(c * mp, ROWS) - c * mp),
                                  (0, 0)))
    grid = (c, lp // b, mp // mc)
    kernel = functools.partial(
        _pileup_kernel, band=b, min_depth=min_depth, l_full=l, m=mp,
        pad_l=pad_l,
    )
    nk = mp // mc
    scal = pl.BlockSpec((mc,), lambda i, j, k: (i * nk + k,),
                        memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    blk = pl.BlockSpec((None, 1, b), lambda i, j, k: (i, 0, j))
    start_p = jnp.pad(start.astype(jnp.int32), ((0, 0), (0, mp - m)))
    plen_p = jnp.pad(plen.astype(jnp.int32), ((0, 0), (0, mp - m)))
    # the scope names the kernel's device time in the profiler trace
    with jax.named_scope("pileup_vote"):
        pol, dep, agr = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[scal, scal, hbm, hbm],
            out_specs=[blk, blk, blk],
            out_shape=[jax.ShapeDtypeStruct((c, 1, lp), jnp.int32)] * 3,
            scratch_shapes=[
                pltpu.VMEM((4, b), jnp.int32),
                pltpu.VMEM((ROWS, b + 2 * LANES), jnp.uint8),
                pltpu.VMEM((ROWS, b + 2 * LANES), jnp.uint8),
                pltpu.SemaphoreType.DMA(()),
            ],
            interpret=interpret,
            name="pileup_vote",
        )(start_p.reshape(-1), plen_p.reshape(-1), draft_p, pieces_p)
    return (
        pol[:, 0, :l].astype(jnp.uint8), dep[:, 0, :l], agr[:, 0, :l],
    )
