"""Sort-based k-mer counting and A-matrix construction (paper §IV-C/D).

Hardware adaptation (DESIGN.md §2): HipMer-style distributed hash tables are
replaced by one global sort of the packed canonical k-mer stream — on TPU the
sort plays the role of the MPI_Alltoallv exchange (keys are "routed" to their
sorted position) and gives exact counts, unique ranks, reliable-k-mer
selection and A-matrix column ids in a single fused pass:

  sort (hi, lo) → run boundaries → per-run counts → reliable runs
       → compact reliable-unique rank = A column id → scatter back via the
         inverse permutation; A from the reads' rows, Aᵀ along the sort.

K-mer selection keeps frequencies in [lower, upper]: singletons are sequencing
errors, high-frequency k-mers are repeats (BELLA's reliable k-mer criterion;
the paper uses max frequency 4 for its experiments).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..core.semiring import Semiring
from ..core.spmat import NO_COL, EllMatrix

# "keep-first" semiring used to build A / Aᵀ (duplicate (row,col) instances of
# a k-mer within the same read keep the first position).
first_semiring = Semiring(
    name="first_pos",
    mul=lambda a, b: {"pos": a["pos"] + 0 * b["pos"]},
    add=lambda x, y: x,
    zero=lambda s: {"pos": jnp.full(s, -1, jnp.int32)},
    is_zero=lambda v: v["pos"] < 0,
)


class KmerCount(NamedTuple):
    """Fused counting result (flat (n·P,) instance-aligned arrays, plus the
    sort permutation)."""

    read_id: jnp.ndarray
    pos_code: jnp.ndarray  # pos*2 + strand
    col_id: jnp.ndarray  # compact reliable-kmer id, -1 if unreliable
    count: jnp.ndarray  # frequency of this instance's k-mer
    reliable: jnp.ndarray  # bool
    order: jnp.ndarray  # instance ids in (k-mer, instance) order
    m_reliable: jnp.ndarray  # scalar: number of reliable unique k-mers
    n_unique: jnp.ndarray  # scalar
    n_singleton: jnp.ndarray  # scalar


@partial(jax.jit, static_argnames=("k", "lower", "upper"))
def count_and_select(
    kmers: dict, *, k: int, lower: int = 2, upper: int = 8
) -> KmerCount:
    """See module docstring. ``kmers`` is the dict from
    ``extract_kmers(..., k=k)``."""
    n, p = kmers["hi"].shape
    e = n * p
    valid = kmers["valid"].reshape(e)
    read_id = jnp.broadcast_to(jnp.arange(n)[:, None], (n, p)).reshape(e)
    pos_code = (kmers["pos"] * 2 + kmers["strand"]).reshape(e)

    big = jnp.int32(2**30)
    # the lo word is zero while the k-mer fits the hi word (k ≤ 15): one sort
    # key then.  XLA's TPU compile time of a long 1-D sort grows with its key
    # and operand count (minutes for two keys past a million instances).
    words = ("hi",) if k <= 15 else ("hi", "lo")
    keys = tuple(jnp.where(valid, kmers[w].reshape(e), big) for w in words)
    *sk, order = jax.lax.sort(
        (*keys, jnp.arange(e, dtype=jnp.int32)), num_keys=len(keys),
        is_stable=True,
    )
    vs = valid[order]

    new_run = jnp.zeros((e,), bool)
    for ks in sk:
        prev = jnp.concatenate([jnp.full((1,), -1, ks.dtype), ks[:-1]])
        new_run = new_run | (ks != prev)

    idx = jnp.arange(e)
    # cumulative max/min (not associative_scan: its TPU compile time grows
    # with the instance count, minutes past a million)
    run_start = jax.lax.cummax(jnp.where(new_run, idx, -1))
    next_new = jnp.concatenate([new_run[1:], jnp.ones((1,), bool)])
    run_end = jax.lax.cummin(jnp.where(next_new, idx, e), reverse=True)
    count_s = jnp.where(vs, run_end - run_start + 1, 0)

    reliable_s = vs & (count_s >= lower) & (count_s <= upper)
    # compact id: rank among reliable runs
    rel_run_start = new_run & reliable_s
    col_s = jnp.cumsum(rel_run_start.astype(jnp.int32)) - 1
    col_s = jnp.where(reliable_s, col_s, -1)

    m_reliable = jnp.sum(rel_run_start.astype(jnp.int32))
    n_unique = jnp.sum((new_run & vs).astype(jnp.int32))
    n_singleton = jnp.sum((new_run & vs & (count_s < lower)).astype(jnp.int32))

    inv = jnp.zeros((e,), jnp.int32).at[order].set(jnp.arange(e, dtype=jnp.int32))
    return KmerCount(
        read_id=read_id,
        pos_code=pos_code,
        col_id=col_s[inv],
        count=count_s[inv],
        reliable=reliable_s[inv],
        order=order,
        m_reliable=m_reliable,
        n_unique=n_unique,
        n_singleton=n_singleton,
    )


@partial(jax.jit, static_argnames=("n_reads", "m_capacity", "read_capacity", "kmer_capacity"))
def build_matrices(
    kc: KmerCount,
    *,
    n_reads: int,
    m_capacity: int,
    read_capacity: int,
    kmer_capacity: int,
):
    """Build A (reads × k-mers, value = pos*2+strand) and Aᵀ from the fused
    counting result.  ``kmer_capacity`` should equal the ``upper`` frequency
    bound — the paper's frequency cap is what makes Aᵀ's row capacity exact.
    Returns (A, Aᵀ, overflow_a, overflow_at).

    Both are what ``from_coo`` builds from the instance triplets (duplicate
    (row, col) entries keep the first instance), without its global
    two-key sort: A's rows are the reads' own (n, P) instance rows, sorted
    row by row; Aᵀ's entries already lie in (k-mer, read) order along the
    counting sort's permutation."""
    e = kc.col_id.shape[0]
    p = e // n_reads
    ok = kc.reliable & (kc.col_id >= 0)

    # A: each read's row sorted by column (stable: equal columns keep their
    # instance order), the first instance of each column kept, then the
    # first ``read_capacity`` kept columns.  Two single-key row sorts that
    # carry the positions: an argsort + row gather form took XLA's TPU
    # compiler minutes at these widths.
    big = jnp.int32(2**30)
    w = max(p, read_capacity)  # rows narrower than the capacity are padded
    key = jnp.pad(jnp.where(ok, kc.col_id, big).reshape(n_reads, p),
                  ((0, 0), (0, w - p)), constant_values=big)
    pos = jnp.pad(kc.pos_code.reshape(n_reads, p), ((0, 0), (0, w - p)))
    key, pos = jax.lax.sort((key, pos), dimension=1, num_keys=1,
                            is_stable=True)
    prev = jnp.concatenate([jnp.full((n_reads, 1), -1, key.dtype),
                            key[:, :-1]], axis=1)
    kept = (key != prev) & (key < big)
    key, pos = jax.lax.sort((jnp.where(kept, key, big), pos), dimension=1,
                            num_keys=1, is_stable=True)
    key, pos = key[:, :read_capacity], pos[:, :read_capacity]
    a = EllMatrix(cols=jnp.where(key < big, key, NO_COL),
                  vals={"pos": jnp.where(key < big, pos, -1)},
                  n_cols=m_capacity)
    ovf_a = jnp.sum(jnp.maximum(jnp.sum(kept, axis=1) - read_capacity, 0))

    # Aᵀ: each reliable k-mer's instances are contiguous along ``order`` in
    # (read, position) order; the first instance of each (k-mer, read) is
    # its entry, and its slot is its rank among them within the k-mer
    ok_s = ok[kc.order]
    col_s = jnp.where(ok_s, kc.col_id[kc.order], -1)
    read_s = kc.read_id[kc.order]
    prev_col = jnp.concatenate([jnp.full((1,), -2, jnp.int32), col_s[:-1]])
    prev_read = jnp.concatenate([jnp.full((1,), -2, read_s.dtype), read_s[:-1]])
    first = ok_s & ((col_s != prev_col) | (read_s != prev_read))
    before = jnp.cumsum(first.astype(jnp.int32)) - first  # entries before
    run_base = jnp.zeros((m_capacity + 1,), jnp.int32).at[
        jnp.where(ok_s & (col_s != prev_col), col_s, m_capacity)
    ].set(before, mode="drop")
    slot = before - run_base[jnp.where(ok_s, col_s, m_capacity)]
    in_cap = first & (slot < kmer_capacity)
    ovf_at = jnp.sum(first & (slot >= kmer_capacity))
    # flat slot index (a 1-D scatter compiles far faster than a 2-D one)
    dst = jnp.where(in_cap, col_s * kmer_capacity + slot,
                    m_capacity * kmer_capacity)
    size = m_capacity * kmer_capacity
    at_cols = jnp.full((size + 1,), NO_COL).at[dst].set(
        read_s.astype(jnp.int32), mode="drop")
    at_pos = first_semiring.zero((size + 1,))["pos"].at[dst].set(
        kc.pos_code[kc.order], mode="drop")
    at = EllMatrix(
        cols=at_cols[:size].reshape(m_capacity, kmer_capacity),
        vals={"pos": at_pos[:size].reshape(m_capacity, kmer_capacity)},
        n_cols=n_reads,
    )
    return a, at, ovf_a, ovf_at
