"""Device-memory (HBM) watermark telemetry (docs/observability.md).

HBM capacity is the current genome-size ceiling (ROADMAP item 3), yet until
this module nothing in the repo *measured* device memory — "smaller" was as
unqueryable as "faster" was before the perf trajectory.  One sampling code
path serves every consumer:

* :func:`sample` takes one :class:`MemorySample` — ``bytes_in_use`` plus the
  best-known ``peak_bytes`` — from ``device.memory_stats()`` where the
  backend reports it (TPU/GPU allocator stats: ``bytes_in_use`` /
  ``peak_bytes_in_use``, maxed over devices since the per-device watermark
  is what binds HBM capacity), falling back to **live-buffer accounting**
  (sum of ``nbytes`` over ``jax.live_arrays()``) on backends that return
  ``None`` (the CPU backend, hence every CI run).  The ``source`` field
  (``"device_stats"`` | ``"live_buffers"``) travels with every number so a
  fallback measurement is never mistaken for an allocator watermark.
* :func:`watermark` is a context manager yielding a :class:`Watermark`:
  every :func:`sample` taken anywhere inside the window — including the
  ones nested spans and nested watermarks take — is folded into the
  window's ``peak_hbm_bytes``, so an outer watermark's peak is at least as
  fine-grained as its inner span boundaries.  On the fallback path the
  peak is therefore *sampled* (span-boundary granularity), not continuous;
  on the device-stats path the allocator's own high-water mark is used.
* ``obs.trace.span`` samples on enter/exit while a memory-enabled
  :class:`~repro.obs.trace.Tracer` is active and attaches
  ``peak_hbm_bytes`` / ``hbm_bytes_in_use`` / ``hbm_delta_bytes`` /
  ``hbm_source`` to the span, so Chrome-trace exports carry HBM columns
  and ``scripts/check_trace.py`` can assert memory attribution on stage
  spans.
* ``benchmarks/_timing.timed`` wraps its calls in a watermark, so every
  benchmark record carries ``peak_hbm_bytes``; the pipeline wraps
  ``assemble`` likewise and emits the ``peak_hbm_bytes``-family stats keys
  (``obs.schema``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Iterator, List, Optional

import jax

#: sample sources: backend allocator stats vs the live-buffer fallback.
SOURCES = ("device_stats", "live_buffers")


@dataclasses.dataclass(frozen=True)
class MemorySample:
    """One point-in-time device-memory reading.

    ``bytes_in_use`` is current allocation; ``peak_bytes`` is the best-known
    high-water mark at sample time (allocator-reported on the device-stats
    path, == ``bytes_in_use`` on the live-buffer fallback); ``source`` names
    the path that produced the numbers."""

    bytes_in_use: int
    peak_bytes: int
    source: str


def _device_stats() -> Optional[MemorySample]:
    """Allocator stats maxed over devices, or None where the backend keeps
    none (``memory_stats()`` returns None on the CPU backend).

    On a TPU the allocator always reports, so a failure there raises: a
    live-buffer number must never stand in for the device watermark."""
    in_use = peak = None
    for dev in jax.devices():
        stats = dev.memory_stats()
        if not stats:
            if dev.platform == "tpu":
                raise RuntimeError(
                    f"{dev} returned no memory_stats(); the TPU allocator "
                    "watermark is unavailable"
                )
            return None
        b = int(stats.get("bytes_in_use", 0))
        p = int(stats.get("peak_bytes_in_use", b))
        in_use = b if in_use is None else max(in_use, b)
        peak = p if peak is None else max(peak, p)
    if in_use is None:  # pragma: no cover - no devices
        return None
    return MemorySample(in_use, max(peak, in_use), "device_stats")


def _live_buffer_bytes() -> int:
    """Total ``nbytes`` of every live device array (the CPU fallback)."""
    total = 0
    for buf in jax.live_arrays():
        try:
            total += int(buf.nbytes)
        except Exception:  # pragma: no cover - deleted buffer race
            pass
    return total


@dataclasses.dataclass
class Watermark:
    """Device-memory accounting for one :func:`watermark` window.

    ``peak_hbm_bytes`` folds every sample taken while the window was open
    (enter/exit plus any nested span or watermark samples);
    ``hbm_bytes_in_use`` is the reading at exit, ``delta_bytes`` the
    exit-minus-enter growth, ``source`` the sampling path."""

    enter: Optional[MemorySample] = None
    exit: Optional[MemorySample] = None
    peak_hbm_bytes: int = 0
    source: str = "live_buffers"

    def _observe(self, s: MemorySample) -> None:
        self.peak_hbm_bytes = max(self.peak_hbm_bytes, s.peak_bytes)
        self.source = s.source

    @property
    def hbm_bytes_in_use(self) -> int:
        """Bytes in use at window exit (0 before the window closed)."""
        return 0 if self.exit is None else self.exit.bytes_in_use

    @property
    def delta_bytes(self) -> int:
        """Exit-minus-enter growth in bytes in use."""
        if self.enter is None or self.exit is None:
            return 0
        return self.exit.bytes_in_use - self.enter.bytes_in_use


#: per-thread registry of open watermark windows: every sample folds into
#: all of the *calling thread's* windows, so outer windows see the sample
#: points their nested spans take while concurrent threads never fold
#: samples into each other's accounting.
_LOCAL = threading.local()


def _open_watermarks() -> List[Watermark]:
    """The calling thread's stack of currently-open watermark windows."""
    try:
        return _LOCAL.open
    except AttributeError:
        out: List[Watermark] = []
        _LOCAL.open = out
        return out


def sample() -> MemorySample:
    """Take one memory sample and fold it into every open watermark.

    Prefers backend allocator stats (``device.memory_stats()``); falls back
    to live-buffer accounting when the backend reports none."""
    s = _device_stats()
    if s is None:
        b = _live_buffer_bytes()
        s = MemorySample(b, b, "live_buffers")
    for w in _open_watermarks():
        w._observe(s)
    return s


@contextlib.contextmanager
def watermark() -> Iterator[Watermark]:
    """Open a device-memory watermark window.

    Yields the :class:`Watermark`; samples on enter and exit, and absorbs
    every sample nested code takes in between (spans under an active
    memory-enabled tracer, nested watermarks, explicit :func:`sample`
    calls)."""
    w = Watermark()
    opened = _open_watermarks()
    opened.append(w)
    try:
        w.enter = sample()
        yield w
    finally:
        try:
            w.exit = sample()
        finally:
            opened.remove(w)
