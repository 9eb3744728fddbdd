"""What decides ``correct``: the last job's output against plain references.

Nothing here imports the assembler.  The references are host numpy:

* ``r_edge_errors`` — R against the simulated truth, on reads drawn from
  the seed.  Expected: every pair of sampled reads whose templates overlap
  by ``[overlap_lo, reach_bp]`` bases, neither template lying within
  another read's (``contained_margin``), where ``reach_bp`` is what the
  cell's configured extension aligns from any seed (``max_steps / 2``
  bases each way).
  Missing expected edges plus false edges (templates that do not overlap,
  wrong relative strand, or a suffix off the truth by more than
  ``suffix_tol``), over the expected count.
* ``s_vs_reference_tr`` — S against Myers' transitive reduction of R
  (combo-resolved, iterated, the configuration's fuzz and iteration cap):
  differing (edge, strand combination) entries.  Exact.
* ``contigs_vs_reference_walk`` — the draft contigs against the unitig walk
  of S (branch cut at any vertex of out- or in-degree above one, cycles cut
  at their least state, reverse-complement twins kept once, isolated
  uncontained reads as singletons): contigs present on one side only or
  with other bases.  Exact.
* ``jobs_differ`` — window jobs whose polished contigs differ from the
  checked job's (all jobs assemble the same reads).  Exact.
* ``job_faults`` — window jobs with an overflowed capacity (``overflow_A``,
  ``overflow_C``, ``overflow_R``, ``tr_overflow``) or a hot op that did not
  run its Pallas kernel.  Exact.
"""

from __future__ import annotations

import hashlib

import numpy as np

import quality as q


def digest(contigs) -> str:
    h = hashlib.sha1()
    for c in contigs:
        h.update(np.asarray([len(c.reads)] + [2 * r + s for r, s in c.reads],
                            np.int64).tobytes())
        h.update(np.asarray(c.codes, np.uint8).tobytes())
    return h.hexdigest()


def outputs(res, polished) -> dict:
    """Host copies of what ``assemble()`` returned that the checks read."""
    return {
        "r_cols": np.asarray(res.r_graph.cols),
        "r_vals": np.asarray(res.r_graph.vals, np.float64),
        "s_cols": np.asarray(res.s_graph.cols),
        "s_vals": np.asarray(res.s_graph.vals, np.float64),
        "contained": np.asarray(res.contained, bool),
        "draft": [(tuple(2 * r + s for r, s in c.reads),
                   np.asarray(c.codes, np.uint8)) for c in res.contigs],
        "polished": polished,
    }


# --------------------------------------------------------------- edges ---

def edge_table(cols, vals):
    """(src, dst, (E, 4) values) of an ELL matrix with MinPlus 4-vectors."""
    i, slot = np.nonzero(cols >= 0)
    v = vals[i, slot]
    keep = np.isfinite(v).any(axis=1)
    return (i[keep].astype(np.int64), cols[i, slot][keep].astype(np.int64),
            v[keep])


def myers_tr(src, dst, val, n: int, fuzz: float, max_iters: int):
    """Iterated Myers rule over every strand combination: entry (i, j, a, b)
    goes when some i→k→j through a middle strand c has suffix sum
    ``v_ik[a, c] + v_kj[c, b] ≤ max finite suffix of row i + fuzz``."""
    val = val.copy()
    for _ in range(max_iters):
        fin = np.isfinite(val)
        rowmax = np.full(n, -np.inf)
        np.maximum.at(rowmax, src, np.where(fin, val, -np.inf).max(axis=1))
        order = np.argsort(src * n + dst)
        src, dst, val = src[order], dst[order], val[order]
        start = np.searchsorted(src, np.arange(n + 1))
        key = src * n + dst
        best = np.full(val.shape, np.inf)
        deg = start[dst + 1] - start[dst]  # out-degree of each edge's head
        for lo in range(0, src.shape[0], 1 << 15):
            ids = np.arange(lo, min(lo + (1 << 15), src.shape[0]))
            d = deg[ids]
            e1 = np.repeat(ids, d)
            off = np.arange(e1.shape[0]) - np.repeat(np.cumsum(d) - d, d)
            e2 = start[dst[e1]] + off
            want = src[e1] * n + dst[e2]
            pos = np.minimum(np.searchsorted(key, want), key.shape[0] - 1)
            hit = key[pos] == want
            e1, e2, eij = e1[hit], e2[hit], pos[hit]
            for a in (0, 1):
                for b in (0, 1):
                    s = np.minimum(val[e1, 2 * a] + val[e2, b],
                                   val[e1, 2 * a + 1] + val[e2, 2 + b])
                    np.minimum.at(best[:, 2 * a + b], eij, s)
        marks = np.isfinite(val) & np.isfinite(best) & (
            best <= (rowmax[src] + fuzz)[:, None])
        if not marks.any():
            break
        val = np.where(marks, np.inf, val)
        live = np.isfinite(val).any(axis=1)
        src, dst, val = src[live], dst[live], val[live]
    return src, dst, val


def entries(src, dst, val) -> dict:
    out = {}
    for e in zip(*np.nonzero(np.isfinite(val))):
        out[(int(src[e[0]]), int(dst[e[0]]), int(e[1]))] = float(val[e])
    return out


def count_entry_diffs(a: dict, b: dict) -> int:
    keys = set(a) | set(b)
    return sum(a.get(k) != b.get(k) for k in keys)


# ------------------------------------------------------------- contigs ---

def unitig_walk(s_src, s_dst, s_val, lengths, codes, contained):
    """Canonical unitigs of the state graph of S; each contig as
    ``(states, codes)``, isolated uncontained reads as singletons."""
    n = lengths.shape[0]
    out_edges, in_deg = {}, {}
    has_edge = np.zeros(n, bool)
    for e, combo in zip(*np.nonzero(np.isfinite(s_val))):
        i, j = int(s_src[e]), int(s_dst[e])
        u, v = 2 * i + (combo >> 1), 2 * j + (combo & 1)
        out_edges.setdefault(u, []).append((v, int(s_val[e, combo])))
        in_deg[v] = in_deg.get(v, 0) + 1
        has_edge[i] = has_edge[j] = True
    succ, pred = {}, {}
    for u, es in out_edges.items():
        if len(es) == 1 and in_deg.get(es[0][0], 0) == 1:
            succ[u] = es[0]
            pred[es[0][0]] = u
    seen = set()
    for u in sorted(succ):  # cut every cycle at its least state
        if u in seen:
            continue
        path, on_path, cur = [], set(), u
        while cur in succ and cur not in seen and cur not in on_path:
            path.append(cur)
            on_path.add(cur)
            cur = succ[cur][0]
        seen.update(on_path)
        if cur in on_path:
            del succ[pred.pop(min(path[path.index(cur):]))]
    chains = []
    for u in sorted(out_edges):
        if u in pred:
            continue
        chain, cur = [(u, 0)], u
        while cur in succ:
            cur, suf = succ[cur]
            chain.append((cur, suf))
        chains.append(chain)
    keys = {tuple(s for s, _ in c): c for c in chains}
    kept = [c for key, c in keys.items()
            if not (tuple(s ^ 1 for s in reversed(key)) in keys
                    and tuple(s ^ 1 for s in reversed(key)) < key)]

    def oriented(r, s):
        x = codes[r, : lengths[r]]
        return (3 - x[::-1]) if s else x

    out = []
    for chain in kept:
        seq = []
        for t, (state, suf) in enumerate(chain):
            o = oriented(state >> 1, state & 1)
            suf = min(suf, o.shape[0])
            seq.append(o if t == 0 else o[o.shape[0] - suf:] if suf > 0
                       else o[:0])
        out.append((tuple(s for s, _ in chain),
                    np.concatenate(seq).astype(np.uint8)))
    for i in range(n):
        if not has_edge[i] and not contained[i]:
            out.append(((2 * i,), codes[i, : lengths[i]].astype(np.uint8)))
    return out


def count_contig_diffs(prog, ref) -> int:
    a = {k: v.tobytes() for k, v in prog}
    b = {k: v.tobytes() for k, v in ref}
    diff = len(prog) - len(a) + len(ref) - len(b)  # duplicated chains
    return diff + sum(a.get(k) != b.get(k) for k in set(a) | set(b))


# ----------------------------------------------------------- R vs truth ---

def shadowed(lo, hi, margin: int) -> np.ndarray:
    """Reads whose template lies within another read's, up to ``margin``
    bases at either end."""
    order = np.argsort(lo, kind="stable")
    los, his = lo[order], hi[order]
    # best other read starting no later: the prefix maximum of ends
    prev = np.concatenate([[-1], np.maximum.accumulate(his)[:-1]])
    out = prev >= his - margin
    upto = np.searchsorted(los, los + margin, side="right")
    for t in range(los.shape[0]):  # reads starting up to margin later
        out[t] |= bool((his[t + 1: upto[t]] >= his[t] - margin).any())
    res = np.zeros(lo.shape[0], bool)
    res[order] = out
    return res


def r_edge_errors(src, dst, val, reads, sample, limits):
    """(missing expected + false edges) / expected, over the rows of R of
    the ``sample`` reads."""
    lo, hi = reads.truth_start, reads.truth_end
    strand = reads.truth_strand
    reach = limits["reach_bp"]
    shadow = shadowed(lo, hi, limits["contained_margin"])
    expected = set()
    for i in sample:
        if shadow[i]:
            continue
        ov = np.minimum(hi[i], hi) - np.maximum(lo[i], lo)
        js = np.nonzero((ov >= limits["overlap_lo"]) & (ov <= reach)
                        & ~shadow)[0]
        expected.update((int(i), int(j)) for j in js if j != i)
    in_sample = np.zeros(lo.shape[0], bool)
    in_sample[sample] = True
    have = {(int(src[e]), int(dst[e])): val[e]
            for e in np.nonzero(in_sample[src])[0]}
    false = 0
    tol_a, tol_b = limits["suffix_tol"]
    for (i, j), v in have.items():
        ok = min(hi[i], hi[j]) - max(lo[i], lo[j]) > 0
        for combo in np.nonzero(np.isfinite(v))[0]:
            a, b = combo >> 1, combo & 1
            ok &= (strand[i] ^ strand[j]) == (a ^ b)
            fwd = (strand[i] ^ a) == 0  # walking i along the genome
            truth = hi[j] - hi[i] if fwd else lo[i] - lo[j]
            ok &= abs(v[combo] - truth) <= tol_a * max(truth, 0) + tol_b
        false += not ok
    missing = sum(k not in have for k in expected)
    return (missing + false) / max(1, len(expected)), {
        "expected": len(expected), "missing": missing, "false": false,
        "edges": len(have),
        "r_max_degree": int(np.bincount(src).max()) if src.size else 0}


# -------------------------------------------------------------- compare ---

def sample_reads(n: int, seed: int, size: int) -> np.ndarray:
    return np.sort(np.random.default_rng([seed, 1]).choice(
        n, size=min(n, size), replace=False))


def compare(out, reads, config, limits, seed, jobs):
    """Every compared number with its limit, ``{name: (value, limit)}``,
    and the counts behind ``r_edge_errors``."""
    p = config["pipeline"]
    n = reads.n_reads
    r = edge_table(out["r_cols"], out["r_vals"])
    s = edge_table(out["s_cols"], out["s_vals"])
    ref_s = myers_tr(*r, n, p["tr_fuzz"], p["tr_max_iters"])
    tr_diff = count_entry_diffs(entries(*s), entries(*ref_s))
    walk = unitig_walk(*s, reads.lengths, reads.codes, out["contained"])
    contig_diff = count_contig_diffs(out["draft"], walk)
    sample = sample_reads(n, seed, limits["sample_reads"])
    err, detail = r_edge_errors(*r, reads, sample, limits)
    last = jobs[-1]["digest"]
    return {
        "job_faults": (sum(bool(j["faults"]) for j in jobs), 0),
        "jobs_differ": (sum(j["digest"] != last for j in jobs), 0),
        "s_vs_reference_tr": (tr_diff, 0),
        "contigs_vs_reference_walk": (contig_diff, 0),
        "r_edge_errors": (err, limits["r_edge_errors"]),
    }, detail


def quality(out, reads, seed, limits) -> dict:
    polished = out["polished"]
    ident, bases = q.sampled_identity(
        polished, reads, np.random.default_rng([seed, 2]),
        limits["identity_bases"], limits["identity_band"])
    return {"n50_bp": q.n50([c.length for c in polished]),
            "contig_identity": ident, "identity_bases": bases,
            "n_contigs": len(polished)}
