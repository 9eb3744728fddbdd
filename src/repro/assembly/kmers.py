"""K-mer extraction and canonicalization (paper §IV-C).

Reads are (n, L_max) uint8 code arrays (A=0, C=1, G=2, T=3) with per-read
lengths.  K-mers are packed 2 bits/base into a (hi, lo) pair of int32 words
(hi: bases 0–14, lo: bases 15–29), supporting k ≤ 30 without 64-bit types
(jax x64 stays off so the LM substrate keeps default dtypes).  The canonical
form is the lexicographic min of the k-mer and its reverse complement; each
instance also carries the strand bit c (0 ⟺ canonical == forward), which the
aligner uses to orient read pairs (s_pair = c_i XOR c_j).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

COMPLEMENT = 3  # complement(code) = 3 - code
BASES = "ACGT"


def encode_seq(s: str) -> jnp.ndarray:
    lut = {c: i for i, c in enumerate(BASES)}
    return jnp.asarray([lut.get(c, 0) for c in s.upper()], jnp.uint8)


def decode_seq(codes) -> str:
    import numpy as np

    return "".join(BASES[int(c)] for c in np.asarray(codes))


def revcomp(codes: jnp.ndarray, length: jnp.ndarray | int) -> jnp.ndarray:
    """Reverse-complement of padded code rows (padding stays at the end).
    Works batched: codes (..., L), length (...)."""
    lmax = codes.shape[-1]
    idx = jnp.asarray(length)[..., None] - 1 - jnp.arange(lmax)
    safe = jnp.clip(idx, 0, lmax - 1)
    idx_b = jnp.broadcast_to(safe, codes.shape)
    out = COMPLEMENT - jnp.take_along_axis(
        codes.astype(jnp.int32), idx_b, axis=-1
    )
    return jnp.where(idx >= 0, out, 0).astype(jnp.uint8)


def _pack(window_codes, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pack k int32 code arrays (base 0 first) into (hi, lo) int32 words,
    15 bases per word, big-endian within the word so (hi, lo) ordering is
    lexicographic."""
    assert k <= 30, "k ≤ 30 supported (2×15 bases in int32)"
    k_hi = min(k, 15)
    hi = jnp.zeros(window_codes[0].shape, jnp.int32)
    for t in range(k_hi):
        hi = hi * 4 + window_codes[t]
    # left-align so shorter-than-15 prefixes still compare lexicographically
    hi = hi * (4 ** (15 - k_hi))
    lo = jnp.zeros(window_codes[0].shape, jnp.int32)
    for t in range(k_hi, k):
        lo = lo * 4 + window_codes[t]
    lo = lo * (4 ** (15 - max(0, k - 15)))
    return hi, lo


@partial(jax.jit, static_argnames=("k",))
def extract_kmers(codes: jnp.ndarray, lengths: jnp.ndarray, *, k: int):
    """All canonical k-mer instances of each read.

    Returns dict with (n, P) arrays where P = L_max − k + 1 rounded up to a
    multiple of 128 (flattening (n, P) is then cheap for XLA's TPU compiler;
    an unaligned P cost it minutes at bacterial sizes):
      hi, lo  — packed canonical k-mer
      strand  — 0 if canonical == forward k-mer else 1
      pos     — start position in the (forward) read
      valid   — position in range
    """
    n, lmax = codes.shape
    p = -(-max(lmax - k + 1, 1) // 128) * 128
    pos = jnp.arange(p)
    c = jnp.pad(codes.astype(jnp.int32), ((0, 0), (0, p + k - 1 - lmax)))
    # base t of every window is the column slice c[:, t : t + P]; packing
    # slice by slice never materializes the (n, P, k) window tensor
    base = [c[:, t : t + p] for t in range(k)]
    fwd_hi, fwd_lo = _pack(base, k)
    rc_hi, rc_lo = _pack([COMPLEMENT - b for b in reversed(base)], k)
    fwd_smaller = (fwd_hi < rc_hi) | ((fwd_hi == rc_hi) & (fwd_lo <= rc_lo))
    hi = jnp.where(fwd_smaller, fwd_hi, rc_hi)
    lo = jnp.where(fwd_smaller, fwd_lo, rc_lo)
    strand = (~fwd_smaller).astype(jnp.int32)
    valid = pos[None, :] < (lengths[:, None] - k + 1)
    return {
        "hi": hi,
        "lo": lo,
        "strand": strand,
        "pos": jnp.broadcast_to(pos[None, :], (n, p)).astype(jnp.int32),
        "valid": valid,
    }
