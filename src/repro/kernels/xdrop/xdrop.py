"""Pallas TPU kernel: banded x-drop seed-extension wavefront.

Hardware adaptation (DESIGN.md §2): SeqAn's SSE anti-diagonal vectorization
becomes a (BAND, PAIRS) wavefront living in VMEM/VREGs — the band runs down
the sublanes and a block of 128·k pairs fills the lanes, so every VPU op
advances every band cell of 128 alignments at once.  The DP state is two
wavefronts + running best (score, ai, bj); fixed trip count (max_steps) with
x-drop retirement masking — identical semantics to the oracle.

Sequence layout.  At step ``s`` band row ``r`` (diagonal ``d = r − c``)
reads ``a[(s + d) // 2]`` and ``b[(s − d) // 2]``.  The wrapper stages each
pair's extension text *doubled* (every base twice, so ``(s + d) // 2``
becomes the linear row ``s + r``) and already walked in the pair's direction
— ``b`` additionally reversed — so the kernel's per-step fetch is one
contiguous dynamic sublane window of each staged text.  No in-kernel gather,
no lane shuffle: the TPU compiler lowers it as plain vector loads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.backend import resolve_interpret

NEG = -(10**9) // 2  # plain int: Pallas kernels cannot capture traced consts
LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _xdrop_kernel(
    la_ref, lb_ref, a2_ref, b2_ref, score_ref, ai_ref, bj_ref, h_ref,
    *, band: int, s_max: int, max_steps: int, xdrop: int, match: int,
    mismatch: int, gap: int,
):
    wp, pb = h_ref.shape[1], h_ref.shape[2]
    c = band // 2
    la = la_ref[...]  # (1, PB)
    lb = lb_ref[...]
    # the two live wavefronts sit in VMEM scratch: step s reads H[s−2] from
    # slot s % 2 and H[s−1] from the other slot, then overwrites H[s−2]
    r0 = jax.lax.broadcasted_iota(jnp.int32, (wp, pb), 0)
    h_ref[0] = jnp.where(r0 == c, 0, NEG).astype(jnp.int32)  # origin, s−2
    h_ref[1] = jnp.full((wp, pb), NEG, jnp.int32)

    def step_fn(s, carry):
        best, bi, bj, alive = carry
        h2 = h_ref[s % 2]
        h1 = h_ref[(s + 1) % 2]
        r = jax.lax.broadcasted_iota(jnp.int32, (wp, pb), 0)
        sf = jnp.minimum(s, s_max)  # past s_max no cell is valid
        av = a2_ref[pl.ds(sf, wp), :]
        bv = b2_ref[pl.ds(s_max - sf, wp), :]
        t = s + r - c  # = 2i, or 2i + 1 off-parity
        i = t >> 1
        j = (s - r + c) >> 1
        valid = (
            ((t & 1) == 0) & (r < band) & (i >= 0) & (i < la)
            & (j >= 0) & (j < lb)
        )
        sub = jnp.where(av == bv, match, mismatch)
        diag = h2 + sub
        # row shifts along the band; the wrapped-in row is a padding row
        # (always NEG), exactly the oracle's NEG fill at the band edges
        up = pltpu.roll(h1, 1, 0) + gap
        left = pltpu.roll(h1, wp - 1, 0) + gap
        h = jnp.maximum(diag, jnp.maximum(up, left))
        h = jnp.where(valid, h, NEG)
        h = jnp.where(h < best - xdrop, NEG, h)
        h = jnp.where(alive > 0, h, NEG)
        h_ref[s % 2] = h
        m = jnp.max(h, axis=0, keepdims=True)  # (1, PB)
        # first row holding the max == the oracle's argmax tie-break
        am = jnp.min(jnp.where(h == m, r, wp), axis=0, keepdims=True)
        improved = m > best
        best2 = jnp.where(improved, m, best)
        bi2 = jnp.where(improved, ((s + am - c) >> 1) + 1, bi)
        bj2 = jnp.where(improved, ((s - am + c) >> 1) + 1, bj)
        alive2 = ((m > NEG) & (s + 1 < la + lb - 1)).astype(jnp.int32)
        return best2, bi2, bj2, alive2

    zero = la * 0
    best, bi, bj, _ = jax.lax.fori_loop(
        0, max_steps, step_fn, (zero, zero, zero, zero + 1)
    )
    score_ref[...] = best
    ai_ref[...] = bi
    bj_ref[...] = bj


def _stage_text(seq, base, step, t):
    """``seq[p, base_p + step_p·t]`` for a (rows, 1) walk index ``t``, laid
    out (rows, pairs): pairs along lanes, walk position down the sublanes."""
    idx = base[None, :] + step[None, :] * t
    idx = jnp.clip(idx, 0, seq.shape[1] - 1)
    return jnp.take_along_axis(seq.T, idx, axis=0).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "band", "max_steps", "xdrop", "match", "mismatch", "gap",
        "pairs_per_block", "interpret",
    ),
)
def xdrop_pallas(
    a, base_a, step_a, len_a, b, base_b, step_b, len_b, *,
    band: int = 33, max_steps: int = 256, xdrop: int = 15, match: int = 1,
    mismatch: int = -1, gap: int = -1, pairs_per_block: int = LANES,
    interpret: bool | str = "auto",
):
    """Batched x-drop extension; ``pairs_per_block`` is rounded up to a
    multiple of the 128-lane width (pairs ride the lanes)."""
    interpret = resolve_interpret(interpret)
    e, lmax_a = a.shape
    lmax_b = b.shape[1]
    pb = _round_up(max(1, min(pairs_per_block, e)), LANES)
    pe = _round_up(e, pb)
    c = band // 2
    wp = _round_up(band + 1, 8)  # ≥ 1 padding row absorbs the roll wrap
    # last fetch start: no cell is valid once s ≥ lmax_a + lmax_b − 1
    s_max = min(max_steps, lmax_a + lmax_b)
    rows = _round_up(s_max + wp, 8)
    s_max = rows - wp

    def lanes(x):
        return jnp.pad(x.astype(jnp.int32), (0, pe - e)).reshape(1, pe)

    # named scopes mark the device work in the profiler trace (op metadata)
    with jax.named_scope("align_staging"):
        u = jnp.arange(rows, dtype=jnp.int32)[:, None]
        # a2[u] = a_text[(u − c) // 2]; b2[v] = b_text[(s_max + c − v) // 2]
        a2 = _stage_text(a, base_a.astype(jnp.int32),
                         step_a.astype(jnp.int32), (u - c) >> 1)
        b2 = _stage_text(b, base_b.astype(jnp.int32),
                         step_b.astype(jnp.int32), (s_max + c - u) >> 1)
        a2 = jnp.pad(a2, ((0, 0), (0, pe - e)))
        b2 = jnp.pad(b2, ((0, 0), (0, pe - e)))
        la, lb = lanes(len_a), lanes(len_b)

    # inside a shard_map the outputs vary over whatever mesh axes the
    # inputs do (the distributed alignment region, core/align_dist.py)
    vma = frozenset().union(*(
        jax.typeof(x).vma for x in (a, base_a, step_a, len_a, b, base_b,
                                    step_b, len_b)
    ))
    kernel = functools.partial(
        _xdrop_kernel, band=band, s_max=s_max, max_steps=max_steps,
        xdrop=xdrop, match=match, mismatch=mismatch, gap=gap,
    )
    row = pl.BlockSpec((1, pb), lambda i: (0, i))
    seq = pl.BlockSpec((rows, pb), lambda i: (0, i))
    # two staged texts, double-buffered, plus headroom for the wavefront
    vmem = 4 * rows * pb * 4 + (4 << 20)
    with jax.named_scope("xdrop_kernel"), jax.named_scope("xdrop_extend"):
        score, ai, bj = pl.pallas_call(
            kernel,
            grid=(pe // pb,),
            in_specs=[row, row, seq, seq],
            out_specs=[row, row, row],
            out_shape=[jax.ShapeDtypeStruct((1, pe), jnp.int32, vma=vma)] * 3,
            scratch_shapes=[pltpu.VMEM((2, wp, pb), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=max(vmem, 16 << 20)
            ),
            interpret=interpret,
            name="xdrop_extend",
        )(la, lb, a2, b2)
    return score[0, :e], ai[0, :e], bj[0, :e]
