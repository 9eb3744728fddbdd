"""Named scopes of the hot device work reach the compiled HLO.

``jax.named_scope`` puts its name into every HLO op's ``op_name``
metadata, which the profiler's device trace carries per op; the benchmark
splits SpGEMM and Alignment by these names.  Compiled here on the CPU
(Pallas in interpret mode): the metadata is the same on every backend.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.semiring import count_semiring as CS, overlap_semiring
from repro.core.spgemm import spgemm
from repro.core.spmat import EllMatrix, from_coo, merge_sorted_rows
from repro.kernels.xdrop.xdrop import xdrop_pallas

MERGE = ["merge_sorted_rows/sort", "merge_sorted_rows/combine",
         "merge_sorted_rows/compact"]


def _count_mat(n, m, cap, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((n, m)) < 0.3
    rows, cols = np.nonzero(mask)
    mat, _ = from_coo(
        jnp.asarray(rows), jnp.asarray(cols),
        jnp.ones(len(rows), jnp.int32), jnp.ones(len(rows), bool),
        n_rows=n, n_cols=m, capacity=cap, semiring=CS,
    )
    return mat


def _spgemm():
    a, b = _count_mat(12, 9, 9, 0), _count_mat(9, 11, 11, 1)
    return spgemm.lower(a, b, semiring=CS, capacity=11)


def _merge():
    cols = jnp.zeros((8, 32), jnp.int32)
    vals = jnp.ones((8, 32), jnp.int32)
    return jax.jit(lambda c, v: merge_sorted_rows(
        c, v, capacity=8, semiring=CS)).lower(cols, vals)


def _from_coo():
    e = jnp.zeros(16, jnp.int32)
    return from_coo.lower(e, e, jnp.ones(16, jnp.int32), e > 0, n_rows=4,
                          n_cols=4, capacity=4, semiring=CS)


def _xdrop():
    seq = jnp.zeros((8, 64), jnp.uint8)
    vec = jnp.zeros(8, jnp.int32)
    return jax.jit(lambda *a: xdrop_pallas(
        *a, band=9, max_steps=32, interpret=True)).lower(
        seq, vec, vec, vec, seq, vec, vec, vec)


@pytest.fixture(params=["default", "assemble"])
def key_flags(request):
    """Lower with JAX's defaults, and as ``assemble()`` does (its compile
    cache keyed on op metadata): the names must survive both."""
    if request.param == "default":
        yield
        return
    from repro.assembly.pipeline import _op_names_in_cache_key

    with _op_names_in_cache_key():
        yield


@pytest.mark.parametrize("lower, scopes", [
    (_spgemm, ["spgemm_expand"] + MERGE),
    (_merge, MERGE),
    (_from_coo, ["from_coo"]),
    (_xdrop, ["align_staging", "xdrop_kernel/xdrop_extend"]),
], ids=["spgemm", "merge_sorted_rows", "from_coo", "xdrop_pallas"])
def test_scopes_in_compiled_op_names(lower, scopes, key_flags):
    text = lower().compile().as_text()
    paths = {"/" + name + "/"
             for name in re.findall(r'op_name="([^"]*)"', text)}
    for scope in scopes:
        assert any(f"/{scope}/" in p for p in paths), (scope, sorted(paths))


def test_expand_and_merge_scopes_do_not_nest():
    """SpGEMM's two parts are timed apart: no op sits under both."""
    text = _spgemm().compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert not [n for n in names
                if "spgemm_expand" in n and "merge_sorted_rows" in n]


def test_merge_sort_carries_value_planes_without_gathers():
    """The merge's ``sort`` scope carries every value plane through a row
    sort keyed on the column, one (key, plane) sort per plane: no gather
    applies a sort order to the candidates there (on a TPU such full-width
    gathers cost many times the sort), and no sort there has more than two
    operands (XLA's TPU compiler lays such a sort along the lanes, and its
    code generation takes minutes at the pipeline's widths)."""
    rng = np.random.default_rng(0)

    def mat(n, m, k):
        cols = np.sort(rng.integers(0, m, (n, k)), axis=1)
        pos = rng.integers(0, 99, (n, k))
        return EllMatrix(cols=jnp.asarray(cols, jnp.int32),
                         vals={"pos": jnp.asarray(pos, jnp.int32)}, n_cols=m)

    text = spgemm.lower(mat(12, 9, 4), mat(9, 11, 5),
                        semiring=overlap_semiring,
                        capacity=6).compile().as_text()
    under_sort = [line for line in text.splitlines()
                  if "/merge_sorted_rows/sort/" in line]
    assert under_sort, "the merge_sorted_rows/sort scope is gone"
    assert not [line for line in under_sort if " gather(" in line]
    # cnt + apos (2) + bpos (2) planes, each an (n, K_A·K_B) operand beside
    # the key
    sorts = [re.match(r"\s*%\S+ = \((.*?)\) sort\(", line)
             for line in under_sort]
    widths = [len(re.findall(r"s32\[12,20\]", m.group(1)))
              for m in sorts if m]
    assert widths == [2] * 5, widths
