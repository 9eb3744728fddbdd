"""Hierarchical span tracing for the assembly pipeline (docs/observability.md).

One timing code path for the whole repo: a :func:`span` context manager that

* records host wall-clock on enter/exit (``time.perf_counter``);
* device-syncs on exit when the span was handed an output
  (:meth:`Span.set_output`), so a stage span measures execution rather than
  async dispatch — the ``_tic`` semantics of ``assembly/pipeline.py``, now
  fixed to descend *arbitrary* pytrees including plain (unregistered)
  dataclasses like ``ContigSet``, which ``jax.block_until_ready`` treats as
  opaque leaves and silently skips;
* nests: spans opened while another span is live become its children, so a
  pipeline run produces a tree — stages → shard_map phases → dispatched
  ops.  Spans opened inside a ``jit``-traced function fire at *trace
  time* (host Python still runs), which is exactly when the nesting is
  meaningful; cached jits re-execute without re-tracing and therefore
  without re-emitting their inner spans (a fresh process — e.g. the CI
  smoke run — always traces once);
* optionally forwards every span to ``jax.profiler.TraceAnnotation`` so the
  same structure shows up in an XLA profiler capture
  (``Tracer(annotate=True)``, enabled via ``PipelineConfig.trace``).

Spans work with or without an active :class:`Tracer`: without one they
still time and sync (that is what keeps ``_tic`` a thin wrapper), they are
just not recorded.  Activate a tracer for a region with :func:`tracing`;
export the recorded tree with ``obs.export``.

Spans name host work.  Device work is named by ``jax.named_scope`` inside
the traced functions, which lands in each HLO op's ``op_name`` metadata
and so in the profiler's device trace.  Between the two sits
:func:`readback`, the one path for a blocking device→host read: it counts
every read (:func:`counting_readbacks`) and, under an annotating tracer,
names the read in the profiler trace.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import time
from typing import Any, Dict, Iterator, List, Optional

import jax
import numpy as np


def _device_leaves(obj: Any, seen: set) -> list:
    """Collect every leaf of ``obj`` carrying ``block_until_ready``,
    descending containers *and* plain dataclass instances (which
    ``jax.tree`` treats as opaque leaves)."""
    if obj is None or id(obj) in seen:
        return []
    seen.add(id(obj))
    if isinstance(obj, jax.core.Tracer):
        return []  # inside a jit trace: nothing to sync
    if hasattr(obj, "block_until_ready"):
        return [obj]
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out = []
        for f in dataclasses.fields(obj):
            out.extend(_device_leaves(getattr(obj, f.name, None), seen))
        return out
    out = []
    for leaf in jax.tree.leaves(obj):
        if leaf is obj:
            continue  # jax saw it as one opaque leaf and it is not an array
        out.extend(_device_leaves(leaf, seen))
    return out


def sync(out: Any) -> Any:
    """Block until every device array reachable from ``out`` is ready.

    Unlike raw ``jax.block_until_ready`` this descends plain dataclasses
    (``ContigSet``, ``ConsensusResult``, …), lists of them, and nested
    dicts — any mix of pytrees and unregistered containers.  Tracers (under
    an active jit trace) are skipped.  Returns ``out``."""
    for leaf in _device_leaves(out, set()):
        leaf.block_until_ready()
    return out


@dataclasses.dataclass
class Span:
    """One timed region: name, free-form attributes, wall-clock interval and
    child spans (populated when a :class:`Tracer` is active)."""

    name: str
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    t0: float = 0.0
    t1: Optional[float] = None
    children: List["Span"] = dataclasses.field(default_factory=list)
    _out: Any = dataclasses.field(default=None, repr=False)

    def set_output(self, out: Any) -> Any:
        """Register ``out`` to be device-synced when the span closes (the
        block-until-ready stage-timing contract).  Returns ``out``."""
        self._out = out
        return out

    def annotate(self, **attrs: Any) -> None:
        """Attach extra attributes to the span after it was opened."""
        self.attrs.update(attrs)

    @property
    def duration_s(self) -> float:
        """Span wall-clock in seconds (0.0 while still open)."""
        return 0.0 if self.t1 is None else self.t1 - self.t0

    @property
    def duration_ms(self) -> float:
        """Span wall-clock in milliseconds (0.0 while still open)."""
        return self.duration_s * 1e3

    def walk(self) -> Iterator["Span"]:
        """Yield this span and every descendant, depth-first preorder."""
        yield self
        for child in self.children:
            yield from child.walk()


class Tracer:
    """Collects a forest of :class:`Span` trees for one traced region.

    ``annotate=True`` additionally wraps every span in a
    ``jax.profiler.TraceAnnotation`` so an XLA profiler capture taken around
    the same region shows the identical hierarchy.  ``memory=True`` (the
    default) samples device memory (``obs.memory``) on every span boundary
    and attaches ``peak_hbm_bytes`` / ``hbm_bytes_in_use`` /
    ``hbm_delta_bytes`` / ``hbm_source`` to each span, so exported traces
    carry HBM columns next to the wall-clock ones."""

    def __init__(self, annotate: bool = False, memory: bool = True):
        self.roots: List[Span] = []
        self._stack: List[Span] = []
        self.annotate = annotate
        self.memory = memory
        self.epoch = time.perf_counter()

    def _push(self, sp: Span) -> None:
        (self._stack[-1].children if self._stack else self.roots).append(sp)
        self._stack.append(sp)

    def _pop(self, sp: Span) -> None:
        if self._stack and self._stack[-1] is sp:
            self._stack.pop()

    def spans(self) -> Iterator[Span]:
        """Yield every recorded span, depth-first preorder across roots."""
        for root in self.roots:
            yield from root.walk()

    def find(self, name: str) -> List[Span]:
        """All recorded spans with the given name."""
        return [sp for sp in self.spans() if sp.name == name]


_ACTIVE: Optional[Tracer] = None


def current_tracer() -> Optional[Tracer]:
    """The tracer activated by the innermost :func:`tracing`, or None."""
    return _ACTIVE


@contextlib.contextmanager
def tracing(tracer: Optional[Tracer]):
    """Activate ``tracer`` for the dynamic extent of the with-block.

    Pass ``None`` to run untraced (spans still time + sync — useful to keep
    one code path for the traced and untraced pipeline)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    try:
        yield tracer
    finally:
        _ACTIVE = prev


@contextlib.contextmanager
def span(name: str, **attrs: Any):
    """Open a span: ``with span("SpGEMM", phase="ring_stage", i=s) as sp``.

    Yields the :class:`Span`; on exit the span device-syncs whatever was
    handed to :meth:`Span.set_output`, closes its wall-clock interval, and —
    when a tracer is active — records itself under the enclosing span."""
    tracer = _ACTIVE
    sp = Span(name=name, attrs=dict(attrs))
    ann = None
    wm = None
    if tracer is not None:
        tracer._push(sp)
        if tracer.annotate:
            try:
                ann = jax.profiler.TraceAnnotation(name)
                ann.__enter__()
            except Exception:  # pragma: no cover - profiler unavailable
                ann = None
        if tracer.memory:
            from . import memory as _memory

            wm = _memory.Watermark()
            opened = _memory._open_watermarks()
            opened.append(wm)
            try:
                wm.enter = _memory.sample()
            except Exception:
                # telemetry must not kill the span, and a failed enter
                # sample must not leave the watermark registered (every
                # later sample would fold into it forever): pop it and run
                # the span without memory attribution
                opened.remove(wm)
                wm = None
    sp.t0 = time.perf_counter()
    try:
        yield sp
    finally:
        sync(sp._out)
        sp.t1 = time.perf_counter()
        if wm is not None:
            from . import memory as _memory

            try:
                wm.exit = _memory.sample()
            except Exception:
                pass  # exit attrs degrade to the enter-side numbers
            finally:
                _memory._open_watermarks().remove(wm)
            sp.attrs.setdefault("peak_hbm_bytes", wm.peak_hbm_bytes)
            sp.attrs.setdefault("hbm_bytes_in_use", wm.hbm_bytes_in_use)
            sp.attrs.setdefault("hbm_delta_bytes", wm.delta_bytes)
            sp.attrs.setdefault("hbm_source", wm.source)
        if ann is not None:
            ann.__exit__(None, None, None)
        if tracer is not None:
            tracer._pop(sp)



@dataclasses.dataclass
class ReadbackCount:
    """Blocking device→host reads taken through :func:`readback`."""

    n: int = 0


_READBACKS: contextvars.ContextVar[Optional[ReadbackCount]] = (
    contextvars.ContextVar("readbacks", default=None)
)


@contextlib.contextmanager
def counting_readbacks() -> Iterator[ReadbackCount]:
    """Count every :func:`readback` of a device value made inside the
    block; yields the :class:`ReadbackCount`."""
    count = ReadbackCount()
    token = _READBACKS.set(count)
    try:
        yield count
    finally:
        _READBACKS.reset(token)


def readback(x: Any, site: str) -> np.ndarray:
    """``x`` on the host as a numpy value.

    A device array is a blocking read: it is counted by the enclosing
    :func:`counting_readbacks`, and while an annotating tracer is active it
    runs under ``jax.profiler.TraceAnnotation("readback:<site>")``, so the
    device idle gap it leaves is named in a profiler capture.  A value
    already on the host passes through uncounted."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    count = _READBACKS.get()
    if count is not None:
        count.n += 1
    tracer = _ACTIVE
    if tracer is None or not tracer.annotate:
        return np.asarray(x)
    with jax.profiler.TraceAnnotation(f"readback:{site}"):
        return np.asarray(x)
