"""Kernel-backend dispatch layer (DESIGN.md §2.5).

The pipeline's two compute hot spots — batched x-drop seed extension
(paper §IV-D) and the dense min-plus squares inside transitive reduction
(Algorithm 2) — each exist twice in this repo: a pure-jnp *reference*
implementation (the oracle) and a Pallas TPU *kernel*.  This module is the
single seam that decides, per op, which one runs:

  * ``"reference"`` — the jnp oracle.  Always available, runs anywhere.
  * ``"pallas"``    — the Pallas kernel.  Compiled by the TPU compiler
    (Mosaic) on TPU — every kernel's compile for a v5e chip is a test in
    ``tests/test_chip_compile.py``; on other platforms it runs in interpret
    mode (bit-identical semantics, no speedup) so parity tests and CI
    exercise the exact kernel code path.  One op, ``spgemm_ring_stages``,
    has no TPU lowering yet and runs its oracle there (recorded, see
    below).
  * ``"auto"``      — platform detection: ``"pallas"`` (compiled) when the
    default JAX backend is TPU, ``"reference"`` elsewhere.

Contract
--------
Implementations register under a string op name via :func:`register_op`; the
kernels package registers both backends for every op it provides when it is
imported (``dispatch`` imports it lazily, so ``core`` never depends on
``kernels`` at module-import time and the ``core → kernels → assembly → core``
cycle is broken).  Registered implementations of one op must agree *exactly*
(same outputs bit-for-bit on the same inputs) — asserted by the parity tests
in ``tests/test_kernels.py`` and the golden-assembly test in
``tests/test_backend.py``.  Anything that holds for one backend's output may
therefore be assumed for the other's.

Current ops
-----------
``xdrop_extend``
    ``(a, base_a, step_a, len_a, b, base_b, step_b, len_b, *, xdrop, match,
    mismatch, gap, band, max_steps, pairs_per_block) -> (score, ai, bj)``
    batched single-direction x-drop extension.
``minplus_dense``
    ``(a, b) -> n`` with ``a (M, K, 4)``, ``b (K, N, 4)``, ``n (M, N, 4)``
    f32; the orientation-resolved dense min-plus matmul of Algorithm 2.
``contig_gen``
    ``(s_mat, codes, lengths, contained) -> ContigSet`` — the Contigs stage
    (DESIGN.md §2.7): ``reference`` is the host walk in
    ``assembly/contigs.py``, ``pallas`` the device array path in
    ``assembly/contig_gen.py``; both must produce identical contigs
    (asserted chain-by-chain by ``tests/test_contigs.py``).
``consensus``
    ``(draft, pieces, start, plen, *, min_depth, band, interpret) ->
    (polished, depth, agree)`` — the banded pileup + majority-vote hot loop
    of the consensus stage (DESIGN.md §2.8): ``reference`` is the jnp
    scatter-add oracle, ``pallas`` the column-banded VMEM accumulation
    kernel; integer counts make the parity exact
    (``tests/test_consensus.py``).
``cc_labels``
    ``(cols, *, max_iters) -> (labels, iters)`` — the hook/shortcut
    connected-components rounds (DESIGN.md §2.9): ``reference`` runs one
    XLA gather/scatter round trip per round, ``pallas`` fuses blocks of
    rounds into VMEM-resident kernel calls (``kernels/cc/``); labels agree
    bit-for-bit (``tests/test_components.py``).
``spgemm_ring_stages``
    ``(offsets, a_cols, a_vals, b_cols, b_vals, *, semiring, capacity,
    n_cols_out, interpret) -> (st_cols, st_vals, overflow)`` — a batch of
    ring-SUMMA local SpGEMM stages (DESIGN.md §2.11): ``reference`` runs the
    gather → ⊗ → merge pipeline once per stage, ``pallas`` fuses the whole
    batch into one grid program with the stage outputs VMEM-resident
    (``kernels/spgemm/``); per-stage buffers agree bit-for-bit
    (``tests/test_kernels.py``), and ``core.summa.summa_ring`` dispatches
    between them.

Which implementation ran
------------------------
Every dispatched call records what actually ran for its op — ``"reference"``,
``"pallas"`` (compiled), ``"pallas-interpret"``, or the reason an
implementation took its oracle instead (:func:`note_impl`) — into the
innermost :func:`recording_impls` log.  ``assemble`` opens one per run and
reports it as ``AssemblyResult.stats["op_impls"]``, so no fallback is silent.

Distribution axis
-----------------
Orthogonal to the backend axis, the device contig path has a
*distribution* axis (DESIGN.md §2.9): ``"gspmd"`` leaves partitioning to
the auto-sharder, ``"shard_map"`` runs the doubling middle with explicit
``ppermute``/``psum`` neighbor exchanges (``core/components_dist.py``).
Both must produce bit-identical results — asserted in
``tests/test_distributed.py``.  ``resolve_distribution`` validates the
knob the same way ``resolve_backend`` does.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Callable, Dict, Iterator, List, Optional, Set, Tuple

import jax

from ..obs.trace import span

BACKENDS = ("auto", "reference", "pallas")

DISTRIBUTIONS = ("gspmd", "shard_map")

_REGISTRY: Dict[Tuple[str, str], Callable] = {}

# op -> implementations that ran, for the innermost recording_impls() block
_IMPL_LOG: contextvars.ContextVar[Optional[Dict[str, Set[str]]]] = (
    contextvars.ContextVar("op_impl_log", default=None)
)
# notes an implementation makes about the call it is serving
_CALL_NOTES: contextvars.ContextVar[Optional[List[str]]] = (
    contextvars.ContextVar("op_call_notes", default=None)
)


def resolve_backend(backend: str = "auto") -> str:
    """Resolve a ``PipelineConfig.backend`` value to a concrete backend."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    return backend


def resolve_distribution(distribution: str = "gspmd") -> str:
    """Validate a ``PipelineConfig.distribution`` value (DESIGN.md §2.9).

    Unlike the backend axis there is no ``"auto"``: GSPMD is always safe, so
    the explicit-exchange path is strictly opt-in."""
    if distribution not in DISTRIBUTIONS:
        raise ValueError(
            f"unknown distribution {distribution!r}; "
            f"expected one of {DISTRIBUTIONS}"
        )
    return distribution


def resolve_interpret(interpret: bool | str = "auto") -> bool:
    """Resolve a kernel's ``interpret`` flag: ``"auto"`` means compiled on
    TPU, interpret mode everywhere else."""
    if interpret == "auto":
        return jax.default_backend() != "tpu"
    return bool(interpret)


@contextlib.contextmanager
def recording_impls() -> Iterator[Dict[str, Set[str]]]:
    """Collect ``op -> {implementation}`` for every dispatched call made
    inside the block."""
    log: Dict[str, Set[str]] = {}
    token = _IMPL_LOG.set(log)
    try:
        yield log
    finally:
        _IMPL_LOG.reset(token)


def record_impl(op: str, impl: str) -> None:
    """Add ``impl`` to the active log for ``op`` (no-op outside
    :func:`recording_impls`).  For callers whose dispatch happens inside a
    cached program, which does not re-trace on a later run."""
    log = _IMPL_LOG.get()
    if log is not None:
        log.setdefault(op, set()).add(impl)


def note_impl(impl: str) -> None:
    """Called by a registered implementation that serves the current
    dispatched call with something other than its default — e.g. the
    oracle, past a VMEM budget.  Replaces the default record of the call."""
    notes = _CALL_NOTES.get()
    if notes is not None:
        notes.append(impl)


def default_impl(backend: str) -> str:
    """The record of a call served the ordinary way by ``backend``."""
    if backend == "reference":
        return "reference"
    return "pallas-interpret" if resolve_interpret("auto") else "pallas"


def register_op(op: str, backend: str, fn: Callable) -> Callable:
    """Register ``fn`` as the ``backend`` implementation of ``op``.

    Called by the kernels layer at import time; re-registration overwrites
    (latest wins) so tests can inject instrumented implementations."""
    if backend not in BACKENDS or backend == "auto":
        raise ValueError(f"backend must be 'reference' or 'pallas', got {backend!r}")
    _REGISTRY[(op, backend)] = fn
    return fn


def available_backends(op: str) -> Tuple[str, ...]:
    """Concrete backends registered for ``op`` (sorted; empty if unknown)."""
    _ensure_registered()
    return tuple(sorted(b for (o, b) in _REGISTRY if o == op))


def _ensure_registered() -> None:
    # Default implementations live in repro.kernels (xdrop_extend,
    # minplus_dense) and repro.assembly.contig_gen (contig_gen); importing
    # them triggers their register_op calls.  Lazy so core stays import-light
    # and the core → kernels/assembly → core cycle stays broken.
    from .. import kernels  # noqa: F401
    from ..assembly import contig_gen  # noqa: F401


def dispatch(op: str, backend: str = "auto") -> Callable:
    """Return the implementation of ``op`` for ``backend`` (resolving
    ``"auto"`` by platform).

    The returned callable is the registered implementation wrapped in an
    ``obs.span`` (name ``"op:<op>"``, kind ``"op"``) — the single place
    every dispatched call gets its launch span, so pipeline traces nest
    stage → shard_map phase → op without per-op wiring.  Inside a jit trace
    the span fires at trace time, which is where the nesting lives."""
    b = resolve_backend(backend)
    key = (op, b)
    if key not in _REGISTRY:
        _ensure_registered()
    if key not in _REGISTRY:
        known = sorted({o for (o, _) in _REGISTRY})
        raise KeyError(f"no {b!r} implementation registered for op {op!r}; "
                       f"known ops: {known}")
    fn = _REGISTRY[key]

    @functools.wraps(fn)
    def dispatched(*args, **kwargs):
        with span(f"op:{op}", kind="op", op=op, backend=b):
            token = _CALL_NOTES.set([])
            try:
                out = fn(*args, **kwargs)
            finally:
                notes = _CALL_NOTES.get()
                _CALL_NOTES.reset(token)
            for impl in notes or [default_impl(b)]:
                record_impl(op, impl)
            return out

    return dispatched
