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

from repro.core.semiring import count_semiring as CS
from repro.core.spgemm import spgemm
from repro.core.spmat import from_coo, merge_sorted_rows
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
