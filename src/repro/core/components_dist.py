"""shard_map contig stages: branch cut, pointer doubling, chain ordering
(DESIGN.md §2.9/§2.10).

The GSPMD device contig path (§2.7) leaves the partitioning of every
doubling round to the auto-sharder, which re-materializes the full pointer
arrays on every gather.  This module is the explicitly-distributed variant
following the 2022 diBELLA contig paper's neighbor-communication model: the
(2n,) state arrays are sharded ``P(row_axes)`` over the mesh's grid-row axes
(the same ``("pod", "data")`` convention as ``runtime/sharding.py`` and
SUMMA, §5), and every exchange is explicit: ``ppermute`` ring all-gathers
for the doubling jumps, ``ppermute`` partner exchanges for the sort network,
``psum``/``pmax`` for degree tallies, convergence tests and cut counts.

Two entry points:

* :func:`doubling_shard_map` — the PR 4 surface: one ``shard_map`` covering
  the doubling middle ``break_cycles`` → ``path_components`` →
  ``chain_rank``.
* :func:`contig_stage_shard_map` — the whole Contigs chain stage under a
  *single* ``shard_map`` region: distributed **branch cut** (per-shard
  degree tallies + one ``psum`` round), the doubling middle, and a
  distributed **chain ordering** built on a ring-bitonic merge-split sort
  over ``ppermute`` (§2.10) — replacing the host-shaped global ``lexsort``
  of ``assembly/contig_gen._order_chains`` so
  ``generate_contigs(distribution="shard_map")`` never leaves the mesh
  between sub-stages.

Per-device exchange volume is exactly accountable: each ring all-gather
moves ``n·(P−1)/P`` words, each sort stage ships the local ``(key, rank,
idx)`` triple block (``3·n/P`` words), and the cut phase pays
``CUT_ALLREDUCES`` ring allreduces (reduce-scatter + all-gather ≙ 2 gathers
each).  :func:`exchange_words` / :func:`exchange_words_sort` are the
measured counterparts of the analytic models in
``benchmarks/bench_comm_model.py`` (``words_contig_doubling`` /
``words_chain_sort``; see docs/communication.md).

All arithmetic is the same int32 doubling/sort-key math as
``core/components.py`` and ``assembly/contig_gen.py``, so the results — the
``path_components`` iteration count and the final ContigSet tensors — are
bit-identical to the local/GSPMD path (asserted in
``tests/test_distributed.py``).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, PartitionSpec as P

from ..obs.trace import span
from .components import _log2_ceil, expand_state_rows

# ring all-gathers issued per doubling round, by phase (see module
# docstring).  chain_rank reuses the convergence probe's gathered parent
# vector as the next round's jump table, so it pays 2 gathers per round
# (d + updated par) plus one initial parent gather.
GATHERS_PER_ROUND = {"break_cycles": 2, "path_components": 4, "chain_rank": 2}

# full-vector allreduces of the distributed branch cut: the in-degree tally
# (psum), the pred scatter (pmax over a −1-initialized buffer — in-deg==1
# makes it single-writer) and the in-suffix scatter (psum, single-writer).
# One ring allreduce ≙ reduce-scatter + all-gather = 2 ring gathers of
# n·(P−1)/P words each.
CUT_ALLREDUCES = 3

# words per element shipped by one merge-split hop of the chain sort: the
# (labkey, rank, idx) triple — idx doubles as the stability tie-break *and*
# the payload (it IS the sorted state permutation).
SORT_WORDS = 3

# ineligible-chain sort key of assembly/contig_gen (states whose chain head
# has no out-edges sort after every real label); padded states get +1 so
# they sort strictly last and slice off cleanly.
_SORT_BIG = jnp.int32(2**30)


def infer_row_axes(mesh) -> Tuple[str, ...]:
    """Grid-row axes of ``mesh`` per the ``runtime/sharding.py`` convention:
    the ``("pod", "data")`` axes that are present, else the first axis."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if axes else (mesh.axis_names[0],)


def default_row_mesh():
    """1D ``("data",)`` mesh over all visible devices — the fallback mesh for
    ``distribution="shard_map"`` when the caller did not build one."""
    devs = jax.devices()
    return jax.make_mesh((len(devs),), ("data",), devices=devs,
                         axis_types=(AxisType.Auto,))


def _ring_all_gather(x: jnp.ndarray, axis_name: str, n_shards: int):
    """ppermute ring all-gather: (n/P,) local shard → (n,) global vector.

    ``P−1`` neighbor hops of ``n/P`` words each; device ``j`` receives shard
    ``(j−s) mod P`` on hop ``s`` and re-rolls the stack into global id
    order."""
    if n_shards == 1:
        return x
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    parts = [x]
    cur = x
    for _ in range(n_shards - 1):
        # repro: noqa[R003] — shared helper: callers count these P−1 ring
        # hops analytically (the exchange_words_* models over GATHERS_PER_*
        # constants), not via a trace-time acct dict.
        cur = jax.lax.ppermute(cur, axis_name, perm)
        parts.append(cur)
    stacked = jnp.stack(parts)  # parts[s] holds shard (j − s) mod P
    j = jax.lax.axis_index(axis_name)
    idx = (j - jnp.arange(n_shards, dtype=jnp.int32)) % n_shards
    return jnp.take(stacked, idx, axis=0).reshape((-1,) + x.shape[1:])


def _doubling_phases(succ_l, pred_l, ids_l, gather, psum_all, max_rounds):
    """Shared shard-local body of the doubling middle — ``break_cycles`` →
    ``path_components`` → ``chain_rank`` — parameterized over the exchange
    closures so :func:`doubling_shard_map` and :func:`contig_stage_shard_map`
    run the exact same int32 arithmetic (bit-identical results and iteration
    counts).  Returns ``(succ2, pred2, labels, head, rank, n_cut, pc_iters,
    cr_iters)``."""

    # --- break_cycles: fixed doubling rounds, cut each cycle at its
    # minimum (same element-wise math as components.break_cycles) ---
    def bc_round(_, carry):
        t_l, m_l = carry
        t_g, m_g = gather(t_l), gather(m_l)
        safe = jnp.where(t_l >= 0, t_l, 0)
        m2 = jnp.where(t_l >= 0, jnp.minimum(m_l, m_g[safe]), m_l)
        t2 = jnp.where(t_l >= 0, t_g[safe], -1)
        return t2, m2

    t, m = jax.lax.fori_loop(0, max_rounds, bc_round, (succ_l, ids_l))
    on_cycle = t >= 0
    cut = on_cycle & (succ_l == m)
    n_cut = psum_all(jnp.sum(cut).astype(jnp.int32))
    succ2 = jnp.where(cut, -1, succ_l)
    pred2 = jnp.where(on_cycle & (ids_l == m), -1, pred_l)

    # --- path_components: while-loop doubling with running minima in
    # both directions; the psum'd continue flag replicates the local
    # convergence test exactly (bit-identical iteration count) ---
    def pc_cond(c):
        return c[5] & (c[4] < max_rounds)

    def pc_body(c):
        tf, tb, mf, mb, it, _ = c
        tf_g, mf_g = gather(tf), gather(mf)
        tb_g, mb_g = gather(tb), gather(mb)
        sf = jnp.where(tf >= 0, tf, 0)
        mf2 = jnp.where(tf >= 0, jnp.minimum(mf, mf_g[sf]), mf)
        tf2 = jnp.where(tf >= 0, tf_g[sf], -1)
        sb = jnp.where(tb >= 0, tb, 0)
        mb2 = jnp.where(tb >= 0, jnp.minimum(mb, mb_g[sb]), mb)
        tb2 = jnp.where(tb >= 0, tb_g[sb], -1)
        cont = psum_all(
            (jnp.any(tf2 >= 0) | jnp.any(tb2 >= 0)).astype(jnp.int32)
        ) > 0
        return tf2, tb2, mf2, mb2, it + 1, cont

    cont0 = psum_all(
        (jnp.any(succ2 >= 0) | jnp.any(pred2 >= 0)).astype(jnp.int32)
    ) > 0
    tf, tb, mf, mb, pc_iters, _ = jax.lax.while_loop(
        pc_cond, pc_body,
        (succ2, pred2, ids_l, ids_l, jnp.int32(0), cont0),
    )
    labels = jnp.minimum(mf, mb)

    # --- chain_rank: parent-jumping with distance accumulation.  The
    # gathered parent vector is carried across rounds: the convergence
    # probe's gather doubles as the next round's jump table ---
    par0 = jnp.where(pred2 >= 0, pred2, ids_l)
    d0 = (pred2 >= 0).astype(jnp.int32)
    par0_g = gather(par0)
    cont0r = psum_all(jnp.any(par0_g[par0] != par0).astype(jnp.int32)) > 0

    def cr_cond(c):
        return c[4] & (c[3] < max_rounds)

    def cr_body(c):
        par, d, par_g, it, _ = c
        d_g = gather(d)
        par2 = par_g[par]
        d2 = d + d_g[par]
        par2_g = gather(par2)
        cont = psum_all(
            jnp.any(par2_g[par2] != par2).astype(jnp.int32)
        ) > 0
        return par2, d2, par2_g, it + 1, cont

    head, rank, _, cr_iters, _ = jax.lax.while_loop(
        cr_cond, cr_body, (par0, d0, par0_g, jnp.int32(0), cont0r)
    )

    return succ2, pred2, labels, head, rank, n_cut, pc_iters, cr_iters


def _mesh_closures(mesh, row_axes: Tuple[str, ...]):
    """Exchange closures over ``mesh``'s grid-row axes: nested per-axis ring
    all-gather, multi-axis ``psum``, and the row-axis count P."""
    p = 1
    for a in row_axes:
        p *= mesh.shape[a]

    def gather(x):
        for ax in reversed(row_axes):
            x = _ring_all_gather(x, ax, mesh.shape[ax])
        return x

    def psum_all(x):
        # repro: noqa[R003] — scalar tallies and convergence probes only;
        # excluded from the exchange-words model by design.
        return jax.lax.psum(x, row_axes)

    return gather, psum_all, p


@lru_cache(maxsize=None)
def _make_doubling(mesh, row_axes: Tuple[str, ...], n_pad: int):
    """Build (and cache per (mesh, axes, size)) the jitted shard_map callable
    running the full doubling middle on ``(n_pad,)`` succ/pred shards."""
    gather, psum_all, p = _mesh_closures(mesh, row_axes)
    n_loc = n_pad // p
    max_rounds = _log2_ceil(n_pad) + 1
    spec = P(row_axes)
    rspec = P()

    def f(succ_l, pred_l):
        idx = jnp.int32(0)
        for a in row_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        ids_l = idx * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
        return _doubling_phases(succ_l, pred_l, ids_l, gather, psum_all,
                                max_rounds)

    return jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec, spec, spec, spec, spec, rspec, rspec, rspec),
        )
    )


def exchange_words(n_pad: int, p: int, bc_rounds: int, pc_iters: int,
                   cr_iters: int) -> int:
    """Per-device words exchanged by one doubling middle: each ring
    all-gather ships ``n·(P−1)/P`` words, break_cycles/path_components/
    chain_rank issue 2/4/2 gathers per round (+1 for chain_rank's initial
    parent gather, which seeds both the convergence probe and round 1's
    jump table)."""
    per_gather = n_pad * (p - 1) // p
    gathers = (
        GATHERS_PER_ROUND["break_cycles"] * bc_rounds
        + GATHERS_PER_ROUND["path_components"] * pc_iters
        + GATHERS_PER_ROUND["chain_rank"] * cr_iters
        + 1
    )
    return gathers * per_gather


def doubling_shard_map(
    succ: jnp.ndarray,
    pred: jnp.ndarray,
    *,
    mesh,
    row_axes: Sequence[str] | None = None,
) -> Dict[str, Any]:
    """Distributed doubling middle of the contig stage: ``break_cycles`` →
    ``path_components`` → ``chain_rank`` under one ``shard_map``.

    Args:
      succ / pred: ``(n,)`` int32 functional successor/predecessor pointers
        (``−1`` = none), the branch-cut output of the state graph.
      mesh: the device mesh; arrays are sharded ``P(row_axes)`` over it.
      row_axes: grid-row axes (default: :func:`infer_row_axes`).

    Returns a dict with the same arrays the local doubling produces —
    ``succ``, ``pred`` (cycle-cut), ``labels``, ``head``, ``rank`` — plus
    ``n_cut``, ``cc_iterations`` (bit-identical to the local
    ``path_components`` count), ``cr_iterations``, ``bc_rounds`` and the
    per-device ``exchange_words`` of the whole middle.
    """
    if row_axes is None:
        row_axes = infer_row_axes(mesh)
    row_axes = tuple(row_axes)
    n = succ.shape[0]
    p = 1
    for a in row_axes:
        p *= mesh.shape[a]
    n_pad = -(-n // p) * p
    if n_pad != n:
        fill = jnp.full(n_pad - n, -1, jnp.int32)
        succ = jnp.concatenate([succ, fill])
        pred = jnp.concatenate([pred, fill])
    fn = _make_doubling(mesh, row_axes, n_pad)
    s2, p2, labels, head, rank, n_cut, pc_iters, cr_iters = fn(succ, pred)
    bc_rounds = _log2_ceil(n_pad) + 1
    return {
        "succ": s2[:n],
        "pred": p2[:n],
        "labels": labels[:n],
        "head": head[:n],
        "rank": rank[:n],
        "n_cut": n_cut,
        "cc_iterations": pc_iters,
        "cr_iterations": cr_iters,
        "bc_rounds": bc_rounds,
        "exchange_words": exchange_words(
            n_pad, p, bc_rounds, int(pc_iters), int(cr_iters)
        ),
    }


# ---------------------------------------------------------------------------
# Ring-bitonic chain ordering + end-to-end contig stage (DESIGN.md §2.10).
# ---------------------------------------------------------------------------


def n_sort_stages(p: int) -> int:
    """Comparator stages of the cross-shard sort network over ``p`` shards:
    the bitonic network's ``log₂P·(log₂P+1)/2`` when ``p`` is a power of
    two, else the odd-even transposition fallback's ``p`` stages (see
    :func:`sort_network`).  ``p ≤ 1`` needs no network."""
    if p <= 1:
        return 0
    if p & (p - 1) == 0:
        lg = p.bit_length() - 1
        return lg * (lg + 1) // 2
    return p


def sort_network(p: int) -> List[List[Tuple[int, int]]]:
    """Comparator schedule sorting ``p`` shard-resident blocks ascending by
    linear shard rank.

    Returns a list of stages; each stage is a list of ``(lo, hi)`` shard
    pairs meaning: the pair exchanges blocks, merges, and ``lo`` keeps the
    lower half, ``hi`` the upper (a *merge-split*).  By the sorted-block
    adaptation theorem (Knuth TAOCP 5.3.4, Baudet–Stevenson), replacing
    every compare-exchange of a valid sorting network with a merge-split on
    locally-sorted blocks yields globally sorted blocks — so the schedule is
    exactly a sorting network on ``p`` wires:

    * ``p`` a power of two → Batcher's bitonic network,
      ``log₂P·(log₂P+1)/2`` stages.  Every stage pairs ``i`` with ``i ^ j``
      (single differing rank bit), so each stage is one ``ppermute`` whose
      partner permutation is a fixed-point-free involution — the reason
      bitonic is preferred over the ring-structured odd-even transposition
      network, which needs ``P`` stages (see DESIGN.md §2.10).
    * otherwise → odd-even transposition (``p`` stages, adjacent pairs;
      one shard idles per stage when ``p`` is odd).
    """
    if p <= 1:
        return []
    stages: List[List[Tuple[int, int]]] = []
    if p & (p - 1) == 0:
        k = 2
        while k <= p:
            j = k // 2
            while j >= 1:
                st = []
                for i in range(p):
                    partner = i ^ j
                    if partner > i:
                        # ascending block (min toward low rank) when the k-bit
                        # of i is 0, descending otherwise — Batcher's rule
                        st.append((i, partner) if (i & k) == 0
                                  else (partner, i))
                stages.append(st)
                j //= 2
            k *= 2
    else:
        for r in range(p):
            stages.append([(i, i + 1) for i in range(r % 2, p - 1, 2)])
    return stages


def exchange_words_sort(n_pad: int, p: int) -> int:
    """Per-device words exchanged by the distributed chain ordering: one
    eligibility ring all-gather of out-degrees (``n·(P−1)/P`` words) plus
    ``n_sort_stages(P)`` merge-split hops of the local ``(labkey, rank,
    idx)`` triple block (``SORT_WORDS·n/P`` words each).  Scalar boundary
    shifts and the P-word chain-prefix exchange are ignored, as the psum
    convergence flags are elsewhere.  Data-independent — the network shape
    is fixed by P — so the analytic twin
    (``bench_comm_model.words_chain_sort``) must match it exactly."""
    if p <= 1:
        return 0
    return n_pad * (p - 1) // p + SORT_WORDS * (n_pad // p) * n_sort_stages(p)


def exchange_words_cut(n_pad: int, p: int) -> int:
    """Per-device words of the distributed branch cut: ``CUT_ALLREDUCES``
    full-vector ring allreduces (reduce-scatter + all-gather, 2 ring gathers
    of ``n·(P−1)/P`` words each) in its single ``psum`` round."""
    if p <= 1:
        return 0
    return CUT_ALLREDUCES * 2 * (n_pad * (p - 1) // p)


@lru_cache(maxsize=None)
def _make_contig_stage(mesh, row_axes: Tuple[str, ...], n_read_pad: int,
                       n_reads: int):
    """Build (and cache per (mesh, axes, sizes)) the jitted shard_map
    callable running branch cut → doubling → chain ordering on
    ``(n_read_pad, K)`` string-matrix row shards.  ``n_read_pad`` is a
    multiple of P so every shard holds an even number of states (read pairs
    never split across shards); states ≥ ``2·n_reads`` are padding."""
    gather, psum_all, p = _mesh_closures(mesh, row_axes)
    n_states = 2 * n_read_pad
    n_loc = n_states // p  # even by construction
    max_rounds = _log2_ceil(n_states) + 1
    stages = sort_network(p)
    spec = P(row_axes)
    rspec = P()
    axes = tuple(row_axes)

    def f(cols_l, vals_l):
        idx = jnp.int32(0)
        for a in row_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        ids_l = idx * n_loc + jnp.arange(n_loc, dtype=jnp.int32)

        # --- branch cut: expand local read rows to state rows (row-local,
        # no exchange), tally degrees per shard, one psum round ---
        with span("Contigs", kind="phase", phase="cut"):
            g_cols, g_vals = expand_state_rows(cols_l, vals_l)
            mask = g_cols >= 0
            out_deg_l = jnp.sum(mask, axis=1).astype(jnp.int32)
            tally_to = jnp.where(mask, g_cols, n_states).reshape(-1)
            tally = (
                jnp.zeros(n_states + 1, jnp.int32)
                .at[tally_to]
                .add(1)[:n_states]
            )
            in_deg = psum_all(tally)  # global in-degree, replicated

            tgt = jnp.max(jnp.where(mask, g_cols, -1), axis=1)
            suf = jnp.sum(jnp.where(mask, g_vals, 0.0), axis=1)
            tgt_safe = jnp.where(tgt >= 0, tgt, 0)
            kept = (out_deg_l == 1) & (tgt >= 0) & (in_deg[tgt_safe] == 1)
            succ_l = jnp.where(kept, tgt, -1)
            n_branch_cut = psum_all(
                jnp.sum(out_deg_l) - jnp.sum(kept).astype(jnp.int32)
            )

            # pred / in-suffix: in-deg(target)==1 makes both scatters single-
            # writer, so a −1-init pmax (resp. 0-init psum) equals the local
            # `.at[].set()` exactly; each shard then slices its own chunk back
            scat = jnp.where(kept, succ_l, n_states)
            pred_buf = (
                jnp.full(n_states + 1, -1, jnp.int32)
                .at[scat]
                .max(ids_l)[:n_states]
            )
            pred_l = jax.lax.dynamic_slice(
                # repro: noqa[R003] — contig cut/sort collectives: the
                # schedule is data-independent and counted analytically by
                # exchange_words_cut/_sort in contig_stage_shard_map.
                jax.lax.pmax(pred_buf, axes), (idx * n_loc,), (n_loc,)
            )
            insuf_buf = (
                jnp.zeros(n_states + 1, jnp.float32)
                .at[scat]
                .add(suf)[:n_states]
            )
            insuf_l = jax.lax.dynamic_slice(
                psum_all(insuf_buf), (idx * n_loc,), (n_loc,)
            )
            in_deg_l = jax.lax.dynamic_slice(in_deg, (idx * n_loc,), (n_loc,))
            has_edge_l = (out_deg_l + in_deg_l).reshape(-1, 2).sum(axis=1) > 0

        # --- doubling middle (shared body, §2.9) ---
        with span("Contigs", kind="phase", phase="doubling"):
            succ2, pred2, labels, head, rank, n_cut, pc_iters, cr_iters = (
                _doubling_phases(succ_l, pred_l, ids_l, gather, psum_all,
                                 max_rounds)
            )

        # --- chain ordering: ring-bitonic merge-split sort (§2.10) over
        # the (labkey, rank, idx) triples; idx makes keys globally unique,
        # so the unique sorted order equals the local path's stable
        # lexsort((rank, labkey)) bit for bit ---
        with span("Contigs", kind="phase", phase="sort",
                  sort_stages=len(stages)):
            out_deg_g = gather(out_deg_l)  # eligibility: out_deg[head]
            elig_l = out_deg_g[head] > 0
            labkey = jnp.where(elig_l, labels, _SORT_BIG)
            labkey = jnp.where(ids_l >= 2 * n_reads, _SORT_BIG + 1, labkey)

            order = jnp.lexsort((ids_l, rank, labkey))
            k1, k2, k3 = labkey[order], rank[order], ids_l[order]
            for pairs in stages:
                perm = [pq for ab in pairs for pq in (ab, ab[::-1])]
                role_tab = np.zeros(p, np.int32)
                for lo, hi in pairs:
                    role_tab[lo], role_tab[hi] = 1, -1
                role = jnp.asarray(role_tab)[idx]
                r1 = jax.lax.ppermute(k1, axes, perm)
                r2 = jax.lax.ppermute(k2, axes, perm)
                r3 = jax.lax.ppermute(k3, axes, perm)
                c1 = jnp.concatenate([k1, r1])
                c2 = jnp.concatenate([k2, r2])
                c3 = jnp.concatenate([k3, r3])
                o = jnp.lexsort((c3, c2, c1))
                sel = jnp.where(role >= 0, o[:n_loc], o[n_loc:])
                # an idle shard (odd-P transposition stages) keeps its block
                k1 = jnp.where(role == 0, k1, c1[sel])
                k2 = jnp.where(role == 0, k2, c2[sel])
                k3 = jnp.where(role == 0, k3, c3[sel])

        # chain boundaries: previous element's labkey, shipped across the
        # shard seam by a single-hop ring shift (1 word)
        prev_last = jax.lax.ppermute(
            k1[-1:], axes, [(i, (i + 1) % p) for i in range(p)]
        ) if p > 1 else k1[-1:]
        prev = jnp.concatenate([prev_last, k1[:-1]])
        pos0 = (jnp.arange(n_loc) == 0) & (idx == 0)
        prev = jnp.where(pos0, -1, prev)
        elig_s = k1 < _SORT_BIG
        new_chain = elig_s & (k1 != prev)

        # global chain index: local cumsum + exclusive shard prefix (one
        # psum of a P-word one-hot vector)
        loc_chains = jnp.sum(new_chain).astype(jnp.int32)
        sums = psum_all(jnp.zeros(p, jnp.int32).at[idx].set(loc_chains))
        prefix = jnp.sum(jnp.where(jnp.arange(p) < idx, sums, 0))
        chain_idx = prefix + jnp.cumsum(new_chain.astype(jnp.int32)) - 1
        n_chains = jnp.sum(sums)
        max_chain = jax.lax.pmax(
            jnp.max(jnp.where(elig_s, k2, -1)), axes
        ) + 1

        return (k3, elig_s, k2, chain_idx, new_chain, insuf_l, has_edge_l,
                n_chains, max_chain, n_branch_cut, n_cut, pc_iters, cr_iters)

    return jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec,) * 7 + (rspec,) * 6,
        )
    )


def contig_stage_shard_map(
    s, *, mesh, row_axes: Sequence[str] | None = None
) -> Tuple[Dict[str, Any], Dict[str, int]]:
    """End-to-end distributed chain stage of contig generation: branch cut →
    doubling middle → ring-bitonic chain ordering under a *single*
    ``shard_map`` region (DESIGN.md §2.10) — no GSPMD sub-stage remains.

    Args:
      s: the string matrix S (``EllMatrix``, MinPlus 4-vector values); its
        read rows are padded to a multiple of P and sharded ``P(row_axes)``.
      mesh / row_axes: the device mesh and its grid-row axes (default:
        :func:`infer_row_axes`).

    Returns ``(st, stats)``: ``st`` is the chain-state pytree with exactly
    the keys ``assembly/contig_gen._order_chains`` produces (bit-identical
    values — asserted in ``tests/test_distributed.py``), ``stats`` the
    per-device exchange accounting split by phase (``exchange_words_cut`` /
    ``_doubling`` / ``_sort``, plus the totals and per-phase round counts;
    see docs/communication.md).
    """
    if row_axes is None:
        row_axes = infer_row_axes(mesh)
    row_axes = tuple(row_axes)
    p = 1
    for a in row_axes:
        p *= mesh.shape[a]
    n = s.cols.shape[0]
    k = s.cols.shape[1]
    n_read_pad = -(-n // p) * p
    cols, vals = s.cols, s.vals
    if n_read_pad != n:
        pad = n_read_pad - n
        cols = jnp.concatenate(
            [cols, jnp.full((pad, k), -1, jnp.int32)]
        )
        vals = jnp.concatenate(
            [vals, jnp.full((pad,) + vals.shape[1:], jnp.inf, vals.dtype)]
        )
    fn = _make_contig_stage(mesh, row_axes, n_read_pad, n)
    with span("Contigs", kind="phase", phase="chain_stage", p=p) as sp:
        (state_s, elig_s, rank_s, chain_idx_s, new_chain, insuf, has_edge,
         n_chains, max_chain, n_branch_cut, n_cut, pc_iters, cr_iters) = (
            sp.set_output(fn(cols, vals))
        )
    n2 = 2 * n
    n_pad = 2 * n_read_pad
    st = {
        "state_s": state_s[:n2],
        "elig_s": elig_s[:n2],
        "rank_s": rank_s[:n2],
        "chain_idx_s": chain_idx_s[:n2],
        "new_chain": new_chain[:n2],
        "insuf": insuf[:n2],
        "has_edge": has_edge[:n],
        "n_chains": n_chains,
        "max_chain": max_chain,
        "n_branch_cut": n_branch_cut,
        "cc_iterations": pc_iters,
    }
    bc_rounds = _log2_ceil(n_pad) + 1
    w_cut = exchange_words_cut(n_pad, p)
    w_dbl = exchange_words(n_pad, p, bc_rounds, int(pc_iters), int(cr_iters))
    w_sort = exchange_words_sort(n_pad, p)
    r_dbl = bc_rounds + int(pc_iters) + int(cr_iters)
    r_sort = n_sort_stages(p) + 1  # merge-split stages + eligibility gather
    stats = {
        "exchange_words": w_cut + w_dbl + w_sort,
        "exchange_rounds": 1 + r_dbl + r_sort,
        "exchange_words_cut": w_cut,
        "exchange_words_doubling": w_dbl,
        "exchange_words_sort": w_sort,
        "exchange_rounds_doubling": r_dbl,
        "exchange_rounds_sort": r_sort,
    }
    return st, stats
