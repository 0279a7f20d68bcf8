"""Structured spans: trace/span ids on the profiler's clock.

The Dapper model: every span carries a ``trace_id`` shared by the whole
request and a fresh ``span_id``; the current span rides a contextvar so
nesting needs no plumbing, and a compact **traceparent** string
(``"<trace_id>-<span_id>"``) crosses process boundaries — attached to
``TCPStore._rpc`` frames and ``distributed.rpc`` payloads, rebound on
the server side with :func:`remote_span`, so one request can be
followed wall-to-wall across workers.

Every span enters a ``jax.profiler.TraceAnnotation``, always: whoever
started the ``jax.profiler`` session that is recording (this package's
``Profiler``, a plain ``jax.profiler.start_trace``, the capture server),
the span is a host event of its xplane beside the device's operations.
With no session the annotation costs well under a microsecond (PERF.md
has the chip host's figure). Finished spans additionally land in a
bounded in-memory ring, with start and end as integer nanoseconds of
``time.time_ns()``, the clock the profiler stamps host events with, so a
span in the ring and its event in a trace are the same interval, and
with the id of the thread they ran on (``tid``), so a reader can nest
the intervals of one thread. The ring is read with
:func:`finished_spans` and :func:`last`, or exported as Chrome-trace
JSONL (:func:`export_chrome_trace`, load via ``chrome://tracing`` /
Perfetto "json" mode).
"""
from __future__ import annotations

import contextlib
import contextvars
import json
import os
import random
import threading
import time
import warnings
from collections import deque

from jax.profiler import TraceAnnotation

from .. import profiler as _profiler

__all__ = [
    "Span", "span", "remote_span", "current_span", "current_trace_id",
    "current_traceparent", "finished_spans", "last", "record",
    "clear_finished_spans", "export_chrome_trace",
    "set_span_buffer_capacity",
]

_current: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_span", default=None
)

_buf_lock = threading.Lock()
_finished: deque = deque(maxlen=4096)

# id generation is on the per-step hot path: one os.urandom-seeded PRNG
# at import, then getrandbits per id (no syscall per span). Not
# cryptographic — span ids are correlation keys, not secrets.
_id_rng = random.Random(os.urandom(16))
_id_lock = threading.Lock()


def _new_id(nbytes=8):
    with _id_lock:
        return f"{_id_rng.getrandbits(nbytes * 8):0{nbytes * 2}x}"


class Span:
    """One named range. ``trace_id`` is inherited from the enclosing
    span (or remote parent) and minted fresh at a trace root."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "attrs",
        "start_ns", "end_ns", "tid",
    )

    def __init__(self, name, trace_id=None, parent_id=None, **attrs):
        self.name = name
        self.trace_id = trace_id or _new_id(16)
        self.span_id = _new_id(8)
        self.parent_id = parent_id
        self.attrs = attrs
        self.start_ns = None   # time.time_ns(), the profiler's host clock
        self.end_ns = None     # None while the span is open
        self.tid = None        # the thread it ran on, set on entry

    @property
    def traceparent(self):
        return f"{self.trace_id}-{self.span_id}"

    @property
    def duration_s(self):
        if self.end_ns is None:
            return None
        return (self.end_ns - self.start_ns) * 1e-9

    def to_chrome_event(self):
        """One Chrome-trace "complete" (ph=X) event."""
        return {
            "name": self.name,
            "cat": "paddle_tpu",
            "ph": "X",
            "ts": self.start_ns / 1e3,
            "dur": (self.duration_s or 0.0) * 1e6,
            "pid": os.getpid(),
            "tid": (self.tid or 0) & 0x7FFFFFFF,
            "args": {
                "trace_id": self.trace_id,
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                **self.attrs,
            },
        }

    def __repr__(self):
        return (f"Span({self.name!r}, trace={self.trace_id[:8]}, "
                f"span={self.span_id}, parent={self.parent_id})")


class _SpanScope:
    def __init__(self, sp):
        self.span = sp
        self._token = None
        self._annotation = None

    def __enter__(self):
        sp = self.span
        self._token = _current.set(sp)
        # the annotation innermost, the clock read beside it: the ring's
        # interval and the trace's event are the same one
        self._annotation = TraceAnnotation(sp.name)
        self._annotation.__enter__()
        sp.tid = threading.get_ident()
        sp.start_ns = time.time_ns()
        return sp

    def __exit__(self, *exc):
        sp = self.span
        sp.end_ns = time.time_ns()
        self._annotation.__exit__(None, None, None)
        _current.reset(self._token)
        if _profiler._stats_active():
            # a Profiler in a RECORD state tabulates spans like RecordEvents
            _profiler._record_span(sp.name, sp.duration_s, "user")
        with _buf_lock:
            _finished.append(sp)
        return False


def _child(name, attrs):
    """A span under the current one (a fresh trace root when none)."""
    parent = _current.get()
    if parent is None:
        return Span(name, **attrs)
    return Span(
        name, trace_id=parent.trace_id, parent_id=parent.span_id, **attrs
    )


def span(name, **attrs):
    """Context manager opening a child span of the current one (a fresh
    trace root when there is none)::

        with observability.span("serving.decode", step=i):
            ...
    """
    return _SpanScope(_child(name, attrs))


def record(name, start_ns, end_ns, **attrs):
    """A span that somebody else timed, on the same clock and on this
    thread, and that is already over (``jit_events`` gets JAX's compile
    phases this way): child of the current span, straight into the ring,
    no annotation."""
    sp = _child(name, attrs)
    sp.start_ns, sp.end_ns = int(start_ns), int(end_ns)
    sp.tid = threading.get_ident()
    with _buf_lock:
        _finished.append(sp)
    return sp


def remote_span(name, traceparent, **attrs):
    """Server-side continuation of a propagated trace: opens a span
    whose parent is the remote caller's span. ``traceparent`` is the
    ``"<trace_id>-<span_id>"`` string from the wire; None (caller had
    no active span) degrades to a no-op, so un-traced coordination
    traffic pays nothing."""
    if not traceparent:
        return contextlib.nullcontext()
    try:
        trace_id, parent_id = traceparent.rsplit("-", 1)
    except ValueError:
        return contextlib.nullcontext()
    return _SpanScope(
        Span(name, trace_id=trace_id, parent_id=parent_id, **attrs)
    )


def current_span():
    return _current.get()


def current_trace_id():
    sp = _current.get()
    return None if sp is None else sp.trace_id


def current_traceparent():
    """The propagation string RPC layers attach to outbound calls; None
    when no span is open."""
    sp = _current.get()
    return None if sp is None else sp.traceparent


def finished_spans():
    """Snapshot of the bounded finished-span buffer (newest last)."""
    with _buf_lock:
        return list(_finished)


def last(name, n=1):
    """The newest ``n`` finished spans called ``name``, oldest first, or
    None when the ring holds fewer: it has wrapped, or they never
    finished. How a reader takes exactly the steps of a window without
    a clock: count them, then ask for that many."""
    out = []
    with _buf_lock:
        for sp in reversed(_finished):
            if sp.name == name:
                out.append(sp)
                if len(out) == n:
                    return out[::-1]
    return None


def clear_finished_spans():
    with _buf_lock:
        _finished.clear()


def set_span_buffer_capacity(capacity):
    """Resize the finished-span ring (existing newest entries kept)."""
    global _finished
    with _buf_lock:
        _finished = deque(_finished, maxlen=int(capacity))


def export_chrome_trace(path):
    """Write the finished-span buffer as Chrome-trace JSONL (one event
    object per line). Exporter contract (docs/observability.md): never
    raises into the caller's serving/training loop — failures (and the
    injected ``obs.export`` fault site) degrade to a warning and return
    None; returns ``path`` on success."""
    from ..resilience import faults

    try:
        faults.fire("obs.export", what="chrome_trace", path=path)
        spans = finished_spans()
        with open(path, "w") as f:
            for sp in spans:
                f.write(json.dumps(sp.to_chrome_event()) + "\n")
        return path
    except Exception as e:
        warnings.warn(
            f"chrome-trace export to {path!r} failed (degraded, "
            f"nothing crashed): {e!r}",
            stacklevel=2,
        )
        return None
