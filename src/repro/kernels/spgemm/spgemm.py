"""Pallas TPU kernel: fused ring-SUMMA local SpGEMM stages.

Interpret mode only: the TPU compiler lowers neither the in-kernel sort nor
the multi-vreg gathers, so compiled on a TPU the op runs its oracle
(``ops.NO_TPU_LOWERING``).

Hardware adaptation (DESIGN.md §2.11): the jnp oracle runs one
gather → semiring-⊗ → sort-by-column → segmented-⊕ → compact pipeline per
ring stage, paying a full HBM round trip per stage for the stage's candidate
buffers.  This kernel fuses ``S`` consecutive stages into one grid program:
one ``pallas_call`` loads the stacked A/B panels, runs every stage's row
pipeline with the stage-output ELL block **VMEM-resident across the ring
steps** — the stationary operand of the C-stationary Cannon schedule — and
writes the per-stage buffers back once.

The candidate merge inside each stage calls the exact
``core.spmat.merge_sorted_rows`` code the oracle uses, so the kernel is
bit-for-bit identical to ``ref.py`` (the parity contract of the
``spgemm_ring_stages`` op, asserted by ``tests/test_kernels.py``).  Panel
rebasing offsets are traced per-device values (they depend on the device's
grid coordinates), so they enter as a small int32 input rather than closure
constants — Pallas kernels cannot capture traced consts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.backend import resolve_interpret
from ...core.semiring import Semiring
from ...core.spmat import merge_sorted_rows


def _stage_multiply(ac, av, bc, bv, off, *, semiring, capacity, nb):
    """One ring stage: rebase → gather → ⊗ → merge (the ``core.spgemm``
    row-expansion pipeline, transliterated so it runs on VMEM residents)."""
    n, ka = ac.shape
    kb = bc.shape[1]
    rebased = ac - off
    in_range = (ac >= 0) & (rebased >= 0) & (rebased < nb)
    ac = jnp.where(in_range, rebased, -1)
    a_valid = ac >= 0
    safe = jnp.where(a_valid, ac, 0)
    b_cols_g = bc[safe]  # (n, KA, KB)
    b_vals_g = jax.tree.map(lambda v: v[safe], bv)
    a_vals_e = jax.tree.map(lambda v: v[:, :, None, ...], av)
    cand_vals = semiring.mul(a_vals_e, b_vals_g)
    cand_valid = (
        a_valid[:, :, None] & (b_cols_g >= 0) & ~semiring.is_zero(cand_vals)
    )
    cand_cols = jnp.where(cand_valid, b_cols_g, -1).reshape(n, ka * kb)
    cand_vals = jax.tree.map(
        lambda v: v.reshape((n, ka * kb) + v.shape[3:]), cand_vals
    )
    return merge_sorted_rows(
        cand_cols, cand_vals, capacity=capacity, semiring=semiring
    )


def _spgemm_stages_kernel(
    *refs,
    semiring: Semiring,
    capacity: int,
    stages: int,
    n: int,
    ka: int,
    nb: int,
    kb: int,
    a_treedef,
    b_treedef,
    a_tails,
    b_tails,
    c_tails,
):
    """Kernel body.  ``refs`` = (off, a_cols, *a_leaves, b_cols, *b_leaves)
    inputs followed by (st_cols, *st_leaves, ovf) outputs, every array
    flattened to one ``(1, numel)`` row."""
    na, nbl = len(a_tails), len(b_tails)
    it = iter(refs)
    off_ref = next(it)
    a_cols_ref = next(it)
    a_leaf_refs = [next(it) for _ in range(na)]
    b_cols_ref = next(it)
    b_leaf_refs = [next(it) for _ in range(nbl)]
    out_cols_ref = next(it)
    out_leaf_refs = [next(it) for _ in range(len(c_tails))]
    ovf_ref = next(it)

    off = off_ref[...]  # (1, S)
    a_cols = a_cols_ref[...].reshape(stages, n, ka)
    a_vals = jax.tree.unflatten(
        a_treedef,
        [r[...].reshape((stages, n, ka) + t)
         for r, t in zip(a_leaf_refs, a_tails)],
    )
    b_cols = b_cols_ref[...].reshape(stages, nb, kb)
    b_vals = jax.tree.unflatten(
        b_treedef,
        [r[...].reshape((stages, nb, kb) + t)
         for r, t in zip(b_leaf_refs, b_tails)],
    )

    st_cols, st_vals = [], []
    ovf = jnp.int32(0)
    for s in range(stages):  # static unroll: stage buffers stay in VMEM
        cc, cv, so = _stage_multiply(
            a_cols[s],
            jax.tree.map(lambda v: v[s], a_vals),
            b_cols[s],
            jax.tree.map(lambda v: v[s], b_vals),
            off[0, s],
            semiring=semiring,
            capacity=capacity,
            nb=nb,
        )
        st_cols.append(cc)
        st_vals.append(cv)
        ovf = ovf + so

    out_cols_ref[...] = jnp.stack(st_cols).reshape(1, -1)
    out_leaves = jax.tree.leaves(
        jax.tree.map(lambda *xs: jnp.stack(xs), *st_vals)
    )
    for r, leaf in zip(out_leaf_refs, out_leaves):
        r[...] = leaf.reshape(1, -1)
    ovf_ref[...] = ovf.reshape(1, 1)


@functools.partial(
    jax.jit, static_argnames=("semiring", "capacity", "n_cols_out", "interpret")
)
def spgemm_ring_stages_pallas(
    offsets: jnp.ndarray,
    a_cols: jnp.ndarray,
    a_vals,
    b_cols: jnp.ndarray,
    b_vals,
    *,
    semiring: Semiring,
    capacity: int,
    n_cols_out: int,
    interpret: bool | str = "auto",
):
    """Fused-kernel backend of ``spgemm_ring_stages`` — same signature and
    bit-identical outputs as :func:`~repro.kernels.spgemm.ref
    .spgemm_ring_stages_ref`, one ``pallas_call`` per stage batch.

    Use :func:`~repro.kernels.spgemm.ops.spgemm_ring_stages_pallas` (the
    registered op) in pipeline code: it adds the VMEM-budget fallback this
    raw wrapper does not have.
    """
    del n_cols_out  # output ids are never re-indexed inside the kernel
    interpret = resolve_interpret(interpret)
    stages, n, ka = a_cols.shape
    _, nb, kb = b_cols.shape
    a_leaves, a_treedef = jax.tree.flatten(a_vals)
    b_leaves, b_treedef = jax.tree.flatten(b_vals)
    a_tails = tuple(leaf.shape[3:] for leaf in a_leaves)
    b_tails = tuple(leaf.shape[3:] for leaf in b_leaves)
    zero = semiring.zero((1, 1))
    c_zero_leaves = jax.tree.leaves(zero)
    c_tails = tuple(leaf.shape[2:] for leaf in c_zero_leaves)

    kernel = functools.partial(
        _spgemm_stages_kernel,
        semiring=semiring,
        capacity=capacity,
        stages=stages,
        n=n,
        ka=ka,
        nb=nb,
        kb=kb,
        a_treedef=a_treedef,
        b_treedef=b_treedef,
        a_tails=a_tails,
        b_tails=b_tails,
        c_tails=c_tails,
    )

    def flat(x):
        return x.reshape(1, -1)

    inputs = (
        [flat(offsets.astype(jnp.int32)), flat(a_cols)]
        + [flat(leaf) for leaf in a_leaves]
        + [flat(b_cols)]
        + [flat(leaf) for leaf in b_leaves]
    )
    in_specs = [
        pl.BlockSpec(x.shape, lambda i: (0, 0)) for x in inputs
    ]
    out_elems = [(stages * n * capacity, jnp.int32)]
    for tail, zleaf in zip(c_tails, c_zero_leaves):
        numel = stages * n * capacity
        for t in tail:
            numel *= t
        out_elems.append((numel, zleaf.dtype))
    out_elems.append((1, jnp.int32))  # overflow
    out_specs = [
        pl.BlockSpec((1, numel), lambda i: (0, 0)) for numel, _ in out_elems
    ]
    out_shape = [
        jax.ShapeDtypeStruct((1, numel), dtype) for numel, dtype in out_elems
    ]
    # the scope names the kernel's device time in the profiler trace
    with jax.named_scope("spgemm_ring_stages"):
        outs = pl.pallas_call(
            kernel,
            grid=(1,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name="spgemm_ring_stages",
        )(*inputs)

    st_cols = outs[0].reshape(stages, n, capacity)
    st_leaves = [
        r.reshape((stages, n, capacity) + t)
        for r, t in zip(outs[1:-1], c_tails)
    ]
    st_vals = jax.tree.unflatten(jax.tree.structure(zero), st_leaves)
    ovf = outs[-1][0, 0]
    return st_cols, st_vals, ovf
