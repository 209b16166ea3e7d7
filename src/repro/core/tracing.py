"""The program's spans: where the host spends a served round or an encode.

Tracing is off by default.  Off, :func:`span` returns one shared no-op
context: a global read and a branch, no clock read, no profiler call.
On (:func:`enable`), each span

  * enters ``jax.profiler.TraceAnnotation(name, **ids)``, so a running
    ``jax.profiler`` trace shows it on the host thread's line, on the
    same clock as the device operations; and
  * on exit appends a :class:`Record` to a bounded in-memory buffer on
    ``time.monotonic()``, with the name of the span it is nested in on
    the same thread (``parent``) and the backend compiles that ran on
    this thread while it was the innermost open span (``compiles``).

:func:`record` stores an interval that starts on one thread and ends on
another (a request's queue wait); it goes to the buffer only.
:func:`records` hands the buffer over and clears it; :func:`dropped`
counts records lost to the bound :data:`BOUND`.

Span names, from the request down (ids in brackets):

  trove.serve.queue [request, batch]   submit -> dispatch of its batch
  trove.serve.collect                  dispatcher waiting for requests
  trove.serve.batch [batch, n_real, rung, round]  one micro-batch
  trove.serve.demux [batch]            results split back to requests
  trove.search.score [round]           one round's scoring phase
  trove.search.load                    one chunk load (prefetch thread)
  trove.search.wait                    scorer waiting for that load
  trove.search.tile / .scan            superchunk tile build / scan call
                                       (a device-resident corpus: .scan
                                       only, no load, wait or tile)
  trove.search.reduce [round]          merge + finalize (reduce thread)
  trove.ivf.select / .gather           IVF list selection / row fetch
  trove.encode.tokenize [n]            host tokenization
  trove.encode.run [rung] / .fetch     encode enqueue / device->host copy
  trove.cache.write [n]                embeddings written to the cache
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import NamedTuple

import jax

# records kept between two records() calls; later ones count as dropped
BOUND = 1 << 18

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record(NamedTuple):
    name: str
    t0: float                   # time.monotonic() seconds
    t1: float
    thread: str
    parent: str | None          # enclosing span on the same thread
    ids: dict
    compiles: int = 0


_NOOP = contextlib.nullcontext()
_enabled = False
_listening = False
_records: list[Record] = []
_dropped = 0
_lock = threading.Lock()
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _keep(rec: Record) -> None:
    global _dropped
    with _lock:
        if len(_records) < BOUND:
            _records.append(rec)
        else:
            _dropped += 1


def _on_compile(event: str, secs: float, **_) -> None:
    if _enabled and event == _COMPILE_EVENT:
        stack = getattr(_local, "stack", None)
        if stack:
            stack[-1].compiles += 1


class _Span:
    __slots__ = ("name", "ids", "t0", "parent", "compiles", "_ann")

    def __init__(self, name: str, ids: dict):
        self.name = name
        self.ids = ids
        self.compiles = 0

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        _stack().pop()
        _keep(Record(self.name, self.t0, t1,
                     threading.current_thread().name, self.parent,
                     self.ids, self.compiles))
        return False


def enable() -> None:
    """Start recording, into an empty buffer."""
    global _enabled, _listening, _dropped
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _listening = True
    with _lock:
        _records.clear()
        _dropped = 0
    _enabled = True


def disable() -> None:
    """Stop recording; what was recorded stays until :func:`records`."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def span(name: str, **ids):
    """A context that records ``name`` with ``ids`` while tracing is on;
    the shared no-op context while it is off."""
    if not _enabled:
        return _NOOP
    return _Span(name, ids)


def annotate(**ids) -> None:
    """Add ``ids`` to the innermost open span on this thread (a value
    known only once the span is under way, such as the round a
    micro-batch ran as)."""
    if _enabled:
        stack = getattr(_local, "stack", None)
        if stack:
            stack[-1].ids.update(ids)


def record(name: str, t0: float, t1: float, **ids) -> None:
    """Store ``[t0, t1]`` (``time.monotonic()`` seconds), measured across
    threads, as a record of ``name``."""
    if _enabled:
        _keep(Record(name, t0, t1, threading.current_thread().name, None,
                     ids))


def records() -> list[Record]:
    """The buffered records, oldest first; the buffer is cleared."""
    global _records
    with _lock:
        out, _records = _records, []
    return out


def dropped() -> int:
    """Records lost to :data:`BOUND` since :func:`enable`."""
    return _dropped
