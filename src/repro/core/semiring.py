"""Custom semirings for diBELLA-2D style sparse linear algebra (paper §IV, Alg. 3).

A semiring here is a pair of vectorized callables over *value pytrees* plus an
explicit additive identity.  Values are pytrees of jnp arrays whose leading
dimensions are broadcast dimensions; ``mul``/``add`` must be shape-polymorphic
elementwise maps so the same semiring drives the local ELL SpGEMM, the
distributed SUMMA, and the Pallas block kernels.

Provided semirings
------------------
* ``minplus_orient_semiring`` — the paper's Algorithm-3 MinPlus semiring with
  bidirected-walk validity.  Each value is a ``(..., 4)`` float32 array ``V``
  holding the overlap-suffix length for each (strand-of-left-end,
  strand-of-right-end) combination, ``V[2a+b]`` with ``a,b ∈ {0,1}`` and
  ``inf`` = absent.  ``mul`` is a 2×2 min-plus matrix product — the contraction
  over the middle strand *is* the paper's "heads adjacent to the intermediate
  node must be consistent" check; ``add`` is elementwise min.
* ``overlap_semiring`` — the SpGEMM semiring for ``C = A·Aᵀ`` (paper §IV-D):
  ``mul`` pairs the two positions of a shared k-mer, ``add`` counts shared
  k-mers and concatenates up to ``NUM_POS_PAIRS`` position pairs.
* ``bool_semiring`` / ``count_semiring`` — utility semirings for pattern
  algebra and tests.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.inf
# Number of shared k-mer position pairs kept per read pair ("for this work we
# store two k-mer positions for each read pair", paper §IV-D).
NUM_POS_PAIRS = 2


@dataclasses.dataclass(frozen=True)
class Semiring:
    """A (⊕, ⊗) pair over value pytrees with explicit identity handling.

    Attributes:
      name: human-readable identifier.
      mul: ``(a_vals, b_vals) -> out_vals``; elementwise over broadcast dims.
        May return the additive identity to signal "no contribution" (e.g. an
        orientation-invalid path).
      add: associative, commutative combine of two value pytrees.
      zero: ``(prefix_shape) -> vals`` additive identity with the given
        leading shape.
      is_zero: ``vals -> bool array`` of the broadcast shape; True where the
        value equals the additive identity (entry should be treated as absent).
    """

    name: str
    mul: Callable[[Any, Any], Any]
    add: Callable[[Any, Any], Any]
    zero: Callable[[tuple], Any]
    is_zero: Callable[[Any], jnp.ndarray]


# ---------------------------------------------------------------------------
# MinPlus semiring with bidirected-walk validity (paper Algorithm 3).
# ---------------------------------------------------------------------------


def _mp_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """2×2 min-plus matmul over the trailing orientation axis.

    ``out[2a+b] = min_c a[2*ax+c] + b[2*c+b]``.  A path i→k→j is valid iff the
    strand in which k is used by (i,k) equals the strand used by (k,j); invalid
    combinations contribute the identity (+inf) automatically.
    """
    prefix = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    am = a.reshape(a.shape[:-1] + (2, 2))
    bm = b.reshape(b.shape[:-1] + (2, 2))
    # out[..., x, y] = min_c am[..., x, c] + bm[..., c, y]
    s = am[..., :, :, None] + bm[..., None, :, :]
    out = jnp.min(s, axis=-2)
    return out.reshape(prefix + (4,))


def _mp_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.minimum(a, b)


def _mp_zero(prefix_shape: tuple) -> jnp.ndarray:
    return jnp.full(prefix_shape + (4,), INF, dtype=jnp.float32)


def _mp_is_zero(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(~jnp.isfinite(v), axis=-1)


minplus_orient_semiring = Semiring(
    name="minplus_orient",
    mul=_mp_mul,
    add=_mp_add,
    zero=_mp_zero,
    is_zero=_mp_is_zero,
)


def mp_value(suffix_len, strand_i, strand_j) -> jnp.ndarray:
    """Build a single-orientation MinPlus value: suffix length at combo
    (strand_i, strand_j), inf elsewhere.  Broadcasts over leading dims."""
    suffix_len = jnp.asarray(suffix_len, jnp.float32)
    combo = 2 * jnp.asarray(strand_i, jnp.int32) + jnp.asarray(strand_j, jnp.int32)
    base = jnp.full(suffix_len.shape + (4,), INF, dtype=jnp.float32)
    return base.at[..., :].set(
        jnp.where(
            jnp.arange(4) == combo[..., None], suffix_len[..., None], INF
        )
    )


# ---------------------------------------------------------------------------
# Overlap-detection semiring for C = A·Aᵀ (paper §IV-D).
# ---------------------------------------------------------------------------
# A-values:   {"pos": int32}  — position of the k-mer in the read.
# C-values:   {"cnt": int32, "apos": (NUM_POS_PAIRS,) int32,
#              "bpos": (NUM_POS_PAIRS,) int32}
# ``mul`` turns one shared k-mer into (cnt=1, the position pair);
# ``add`` sums counts and keeps the first NUM_POS_PAIRS pairs (the paper
# concatenates "as long as it is smaller than the number of positions to be
# stored"); with a deterministic merge order this is associative.

# numpy scalar so overlap-semiring code stays Pallas-traceable (a jnp scalar
# would be a captured constant inside kernel bodies, which pallas_call rejects)
_NOPOS = np.int32(-1)


def _ov_mul(a: Any, b: Any) -> Any:
    apos = jnp.asarray(a["pos"], jnp.int32)
    bpos = jnp.asarray(b["pos"], jnp.int32)
    shape = jnp.broadcast_shapes(apos.shape, bpos.shape)
    apos = jnp.broadcast_to(apos, shape)
    bpos = jnp.broadcast_to(bpos, shape)
    pad = jnp.full(shape + (NUM_POS_PAIRS - 1,), _NOPOS)
    return {
        "cnt": jnp.ones(shape, jnp.int32),
        "apos": jnp.concatenate([apos[..., None], pad], axis=-1),
        "bpos": jnp.concatenate([bpos[..., None], pad], axis=-1),
    }


def _take_first_pairs(xa, xb, xn, ya, yb):
    """Concatenate y's pairs after x's xn valid pairs, truncate."""
    # slots: for slot s in [0, NUM_POS_PAIRS): value = xa[s] if s < xn else
    # ya[s - xn].
    s = jnp.arange(NUM_POS_PAIRS)
    xn_b = xn[..., None]
    from_x = s < xn_b
    yidx = jnp.clip(s - xn_b, 0, NUM_POS_PAIRS - 1)
    # y's slot yidx by selects, not a gather: a gather on this 2-wide minor
    # axis inside the segmented scans makes XLA's TPU compile blow up
    ya_s, yb_s = ya[..., :1], yb[..., :1]
    for j in range(1, NUM_POS_PAIRS):
        ya_s = jnp.where(yidx == j, ya[..., j : j + 1], ya_s)
        yb_s = jnp.where(yidx == j, yb[..., j : j + 1], yb_s)
    return jnp.where(from_x, xa, ya_s), jnp.where(from_x, xb, yb_s)


def _ov_add(x: Any, y: Any) -> Any:
    xn = jnp.minimum(x["cnt"], NUM_POS_PAIRS)
    out_a, out_b = _take_first_pairs(x["apos"], x["bpos"], xn, y["apos"], y["bpos"])
    return {"cnt": x["cnt"] + y["cnt"], "apos": out_a, "bpos": out_b}


def _ov_zero(prefix_shape: tuple) -> Any:
    return {
        "cnt": jnp.zeros(prefix_shape, jnp.int32),
        "apos": jnp.full(prefix_shape + (NUM_POS_PAIRS,), _NOPOS),
        "bpos": jnp.full(prefix_shape + (NUM_POS_PAIRS,), _NOPOS),
    }


def _ov_is_zero(v: Any) -> jnp.ndarray:
    return v["cnt"] == 0


overlap_semiring = Semiring(
    name="overlap_pospair",
    mul=_ov_mul,
    add=_ov_add,
    zero=_ov_zero,
    is_zero=_ov_is_zero,
)


# ---------------------------------------------------------------------------
# Utility semirings.
# ---------------------------------------------------------------------------

bool_semiring = Semiring(
    name="bool",
    mul=lambda a, b: jnp.logical_and(a, b),
    add=lambda a, b: jnp.logical_or(a, b),
    zero=lambda s: jnp.zeros(s, bool),
    is_zero=lambda v: ~v,
)

count_semiring = Semiring(
    name="count",
    mul=lambda a, b: (jnp.asarray(a, jnp.int32) * jnp.asarray(b, jnp.int32)),
    add=lambda a, b: a + b,
    zero=lambda s: jnp.zeros(s, jnp.int32),
    is_zero=lambda v: v == 0,
)

plus_times_f32 = Semiring(
    name="plus_times_f32",
    mul=lambda a, b: a * b,
    add=lambda a, b: a + b,
    zero=lambda s: jnp.zeros(s, jnp.float32),
    is_zero=lambda v: v == 0.0,
)


def tree_where(mask: jnp.ndarray, a: Any, b: Any) -> Any:
    """``jnp.where`` lifted to value pytrees; mask broadcasts on leading dims."""

    def _w(x, y):
        m = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))
        return jnp.where(m, x, y)

    return jax.tree.map(_w, a, b)


def tree_take(vals: Any, idx: jnp.ndarray, axis: int = 0) -> Any:
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=axis), vals)
