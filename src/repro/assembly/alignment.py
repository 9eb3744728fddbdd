"""Seed-and-extend x-drop pairwise alignment (paper §IV-D).

SeqAn's SSE x-drop extension is replaced by an anti-diagonal wavefront DP
whose band lives in VREG lanes (and, in the Pallas kernel, VMEM): at step
s = i + j the wavefront holds scores for diagonal offsets d = i − j within a
static band; the three moves are

    diagonal  (i−1, j−1) → H[s−2][d]      + match/mismatch
    up        (i−1, j)   → H[s−1][d−1]    + gap
    left      (i, j−1)   → H[s−1][d+1]    + gap

Cells are valid when (s+d) is even, and cells scoring below ``best − x`` are
retired (x-drop).  The loop exits when the whole wavefront is retired.

This module is the pure-jnp oracle; ``repro.kernels.xdrop`` is the Pallas
version validated against it.  The driver (``extend_pair``) runs forward and
backward extensions from the seed and produces the alignment coordinates the
overlap classifier consumes.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.backend import dispatch

NEG = jnp.int32(-(10**9) // 2)


class Extension(NamedTuple):
    score: jnp.ndarray  # best extension score (0 = empty extension)
    ai: jnp.ndarray  # chars consumed of a
    bj: jnp.ndarray  # chars consumed of b


def _varying_like(tree, *refs):
    """Cast ``tree``'s leaves to vary over every manual mesh axis any of
    ``refs`` varies over, so loop carries seeded from constants type-match
    the carries the body computes from ``refs`` inside a ``shard_map``."""
    axes = tuple(sorted(set().union(*(jax.typeof(r).vma for r in refs))))
    if not axes:
        return tree
    return jax.tree.map(lambda x: jax.lax.pcast(x, axes, to="varying"), tree)


def _fetch(codes, base, step, t, limit):
    """codes[base + step*t] with validity t < limit."""
    idx = base + step * t
    safe = jnp.clip(idx, 0, codes.shape[-1] - 1)
    return codes[safe].astype(jnp.int32), (t >= 0) & (t < limit)


@partial(jax.jit, static_argnames=("band", "max_steps"))
def xdrop_extend(
    a,
    base_a,
    step_a,
    len_a,
    b,
    base_b,
    step_b,
    len_b,
    *,
    xdrop: int = 15,
    match: int = 1,
    mismatch: int = -1,
    gap: int = -1,
    band: int = 33,
    max_steps: int = 512,
) -> Extension:
    """Single-pair x-drop extension (see module docstring).

    ``a[base_a + step_a * t]`` for t ∈ [0, len_a) is the extension text of a
    (step −1 walks backwards from a seed), similarly for b."""
    w = band
    c = w // 2
    offs = jnp.arange(w) - c  # d = i − j per lane

    def step_fn(carry):
        s, h1, h2, best, bi, bj, alive = carry
        i = (s + offs) // 2
        j = (s - offs) // 2
        parity_ok = ((s + offs) % 2) == 0
        ai, va = _fetch(a, base_a, step_a, i, len_a)
        bjv, vb = _fetch(b, base_b, step_b, j, len_b)
        valid = parity_ok & va & vb & (i >= 0) & (j >= 0)
        sub = jnp.where(ai == bjv, match, mismatch)
        diag = h2 + sub
        up = jnp.concatenate([jnp.full((1,), NEG), h1[:-1]]) + gap
        left = jnp.concatenate([h1[1:], jnp.full((1,), NEG)]) + gap
        h = jnp.maximum(diag, jnp.maximum(up, left))
        h = jnp.where(valid, h, NEG)
        h = jnp.where(h < best - xdrop, NEG, h)  # x-drop retirement
        m = jnp.max(h)
        am = jnp.argmax(h)
        improved = m > best
        best2 = jnp.where(improved, m, best)
        bi2 = jnp.where(improved, i[am] + 1, bi)
        bj2 = jnp.where(improved, j[am] + 1, bj)
        return (s + 1, h, h1, best2, bi2, bj2, jnp.any(h > NEG))

    def cond_fn(carry):
        s, _, _, _, _, _, alive = carry
        return alive & (s < jnp.minimum(max_steps, len_a + len_b - 1))

    h1 = jnp.full((w,), NEG)  # wavefront s−1 (empty)
    h2 = jnp.where(offs == 0, 0, NEG)  # virtual origin at s−2
    init = _varying_like(
        (jnp.int32(0), h1, h2, jnp.int32(0), jnp.int32(0), jnp.int32(0),
         jnp.bool_(True)),
        a, base_a, step_a, len_a, b, base_b, step_b, len_b,
    )
    _, _, _, best, bi, bj, _ = jax.lax.while_loop(cond_fn, step_fn, init)
    return Extension(score=best, ai=bi, bj=bj)


class PairAlignment(NamedTuple):
    score: jnp.ndarray
    bi: jnp.ndarray  # [bi, ei) on read i (forward frame)
    ei: jnp.ndarray
    bj: jnp.ndarray  # [bj, ej) on read j (oriented frame)
    ej: jnp.ndarray


@partial(jax.jit, static_argnames=("k", "band", "max_steps"))
def extend_pair(
    a,
    la,
    b_oriented,
    lb,
    pa,
    pb,
    *,
    k: int,
    xdrop: int = 15,
    match: int = 1,
    mismatch: int = -1,
    gap: int = -1,
    band: int = 33,
    max_steps: int = 512,
) -> PairAlignment:
    """Seed-and-extend around an exact k-mer seed at (pa on a, pb on oriented
    b).  Forward from the seed end, backward from the seed start."""
    kw = dict(
        xdrop=xdrop, match=match, mismatch=mismatch, gap=gap, band=band,
        max_steps=max_steps,
    )
    fwd = xdrop_extend(
        a, pa + k, 1, la - pa - k, b_oriented, pb + k, 1, lb - pb - k, **kw
    )
    bwd = xdrop_extend(
        a, pa - 1, -1, pa, b_oriented, pb - 1, -1, pb, **kw
    )
    score = k * match + fwd.score + bwd.score
    return PairAlignment(
        score=score,
        bi=pa - bwd.ai,
        ei=pa + k + fwd.ai,
        bj=pb - bwd.bj,
        ej=pb + k + fwd.bj,
    )


def batch_extend(
    a_codes, a_len, b_codes_oriented, b_len, pa, pb, *, k,
    backend: str = "reference", match: int = 1,
    pairs_per_block: int | None = None, **kw
) -> PairAlignment:
    """Batched seed-and-extend through the kernel-backend dispatch layer
    (core/backend.py): forward and backward extensions each run as one
    batched ``xdrop_extend`` op on the selected backend, then combine into
    the same ``PairAlignment`` as ``extend_pair``."""
    fn = dispatch("xdrop_extend", backend)
    pa = jnp.asarray(pa, jnp.int32)
    pb = jnp.asarray(pb, jnp.int32)
    a_len = jnp.asarray(a_len, jnp.int32)
    b_len = jnp.asarray(b_len, jnp.int32)
    step = jnp.ones(pa.shape, jnp.int32)
    kw = dict(match=match, pairs_per_block=pairs_per_block, **kw)
    fs, fa, fb = fn(
        a_codes, pa + k, step, a_len - pa - k,
        b_codes_oriented, pb + k, step, b_len - pb - k, **kw
    )
    bs, ba, bb = fn(
        a_codes, pa - 1, -step, pa, b_codes_oriented, pb - 1, -step, pb, **kw
    )
    return PairAlignment(
        score=k * match + fs + bs,
        bi=pa - ba,
        ei=pa + k + fa,
        bj=pb - bb,
        ej=pb + k + fb,
    )
