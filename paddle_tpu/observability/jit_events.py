"""Compile/retrace event log for the jit layer.

Every XLA trace in the process — ``jit.to_static`` staging,
``jit.TrainStep``, the serving engine's prefill/decode programs —
records an event (fn, kind, signature, elapsed wall clock) into a
bounded log, increments ``paddle_tpu_jit_compiles_total{kind}``, and
lands in the flight recorder. A trace for a *(fn, signature)* pair that
was already traced once is a **retrace after warmup** — the classic
silent serving-latency killer (a shape or weak type leaked into a hot
path) — and additionally bumps the alarmable
``paddle_tpu_jit_retraces_after_warmup_total{kind}`` counter, turning
"the bench got slow and flaky" into a monitorable signal.

Mechanics: call sites wrap the jitted call in :func:`watch` (host-side,
a thread-local push/pop — nanoseconds when nothing traces) and the
traced body calls :func:`mark_traced` at its top. The body of a
``jax.jit`` function only executes while XLA is TRACING it, so
``mark_traced`` fires exactly on compiles and is free on the warm
path; the enclosing ``watch`` supplies the event's identity and
measures the call's elapsed time, first run included. What JAX itself
times inside that call (``jax.monitoring``: tracing to a jaxpr,
lowering to a module, the backend's compile or the persistent cache's
load) the watch hears on its own thread and records as ``jit.trace``,
``jit.lower`` and ``jit.compile`` spans (``cache_hit=`` on the last)
and as ``trace_s``, ``lower_s``, ``compile_s`` on the event. A compile
on a thread with no ``watch`` open (an eager operator, the optimizer's
zeros, the caller's own ``jax.jit``) is filed as the same three spans
with ``kind="unwatched"`` and ``fn=`` JAX's own name for the function:
outermost intervals only, no event in the log, no counter.

Executables loaded from the persistent compile cache
(``paddle_tpu.compilecache``) are recorded via :func:`mark_aot_hit`
under their own ``kind="aot-hit"``: visible in the log and postmortems,
counted in ``paddle_tpu_jit_aot_hits_total``, but never as a compile or
a retrace — a warm restart reads as zero compile activity.

``suppress()`` masks the hooks for trace-only work: ``analysis.check``
traces programs through the same machinery without ever compiling or
running them, and must not read as compile activity (the same
probe-snapshot discipline ``Engine.check_decode`` applies to the
traced-body compile counters).
"""
from __future__ import annotations

import threading
import time
from collections import deque

import jax.monitoring

from . import metrics as _metrics
from . import spans as _spans

__all__ = [
    "watch", "mark_traced", "mark_aot_hit", "suppress", "compile_log",
    "clear_compile_log", "retraces_after_warmup", "aot_hits",
]

_tls = threading.local()

_lock = threading.Lock()
_log: deque = deque(maxlen=256)
_seen: dict = {}      # (name, kind, signature) -> trace count

_compiles = _metrics.counter(
    "paddle_tpu_jit_compiles_total",
    "XLA traces recorded by the jit layer", ("kind",),
)
_retraces = _metrics.counter(
    "paddle_tpu_jit_retraces_after_warmup_total",
    "traces of a (fn, signature) pair that was already traced once — "
    "a shape/weak-type leak into a warm hot path", ("kind",),
)
_aot_hits = _metrics.counter(
    "paddle_tpu_jit_aot_hits_total",
    "compiled executables loaded from the persistent compile cache "
    "instead of traced (compilecache warm restarts)",
)


def _watch_stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _suppressed():
    return getattr(_tls, "suppress", 0) > 0


class suppress:
    """Mask compile-event recording for the dynamic extent (used by the
    trace-only analyzer so its traces never read as compiles)."""

    def __enter__(self):
        _tls.suppress = getattr(_tls, "suppress", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.suppress -= 1
        return False


# JAX's own duration events -> the phase they are recorded as
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _on_phase_start(event, value, **kwargs):
    """jax.monitoring scalar listener: JAX announces the start of each of
    its timed intervals under the interval's own event. Counted a thread,
    so that the interval's end knows whether it was an outermost one."""
    if event in _PHASES:
        _tls.depth = getattr(_tls, "depth", 0) + 1


def _on_time_span(event, start_s, end_s, fun_name="", **kwargs):
    """jax.monitoring listener, on the thread that compiles. Only the
    outermost intervals are kept, so the phases never count a second
    twice: a jit traced inside another's trace, a helper traced while
    lowering or a constant compiled while tracing belongs to the phase
    that contains it (one trace of a train step holds thousands).

    Under a ``watch`` the interval is the innermost open watch's, filed
    when that closes. With none open it is a compile the program or its
    caller caused some other way (an eager operator, the optimizer's
    zeros, the caller's own ``jax.jit``) and is filed at once, as a
    ``jit.<phase>`` span of ``kind="unwatched"`` under JAX's own name for
    the function: child of whatever span is open."""
    phase = _PHASES.get(event)
    if phase is None:
        return
    depth = _tls.depth = max(getattr(_tls, "depth", 1) - 1, 0)
    hit = False
    if phase == "compile":
        hit = getattr(_tls, "cache_hits", 0) > 0
        _tls.cache_hits = 0
    if _suppressed():
        return
    st = getattr(_tls, "stack", None)
    if st:
        if depth == st[-1]._depth:
            st[-1].phases.append((phase, start_s, end_s, hit))
    elif depth == 0:
        _file_phase(phase, start_s, end_s, hit, fun_name, "unwatched")


def _file_phase(phase, start_s, end_s, hit, fn, kind):
    """One of JAX's intervals into the span ring (its clock is the
    ring's: ``time.time()``)."""
    attrs = {"cache_hit": hit} if phase == "compile" else {}
    _spans.record("jit." + phase, start_s * 1e9, end_s * 1e9,
                  fn=fn, kind=kind, **attrs)


def _on_event(event, **kwargs):
    if event == _CACHE_HIT:
        _tls.cache_hits = getattr(_tls, "cache_hits", 0) + 1


jax.monitoring.register_scalar_listener(_on_phase_start)
jax.monitoring.register_event_time_span_listener(_on_time_span)
jax.monitoring.register_event_listener(_on_event)


class watch:
    """Wrap one jitted call; supplies identity + elapsed time for any
    trace that fires inside it, and hears JAX's compile phases::

        with jit_events.watch("decode", kind="serving", signature="s"):
            out = decode_jit(...)
    """

    def __init__(self, name, kind="jit", signature=""):
        self.name = name
        self.kind = kind
        self.signature = str(signature)
        self.events = []
        self.phases = []      # (phase, start_s, end_s, cache_hit)
        self._depth = 0       # JAX's open intervals when the watch opened
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._depth = getattr(_tls, "depth", 0)
        _watch_stack().append(self)
        return self

    def __exit__(self, *exc):
        st = _watch_stack()
        if st and st[-1] is self:
            st.pop()
        else:  # defensive: unbalanced exits must not corrupt the stack
            try:
                st.remove(self)
            except ValueError:
                pass
        if self.events:
            elapsed = time.perf_counter() - self._t0
            sums = {"trace": 0.0, "lower": 0.0, "compile": 0.0}
            for phase, start_s, end_s, hit in self.phases:
                sums[phase] += end_s - start_s
                _file_phase(phase, start_s, end_s, hit, self.name,
                            self.kind)
            for ev in self.events:
                ev["elapsed_s"] = elapsed
                for phase, seconds in sums.items():
                    ev[phase + "_s"] = seconds
                _emit(ev)
        return False


def mark_traced(name=None, kind=None, signature=None):
    """Called from INSIDE a traced body (runs only while XLA traces).
    Identity defaults come from the enclosing :class:`watch`; an
    unwatched trace is still logged under the explicit (or
    ``<untracked>``) name with no elapsed time."""
    if _suppressed():
        return
    st = _watch_stack()
    w = st[-1] if st else None
    name = name if name is not None else (w.name if w else "<untracked>")
    kind = kind if kind is not None else (w.kind if w else "jit")
    signature = (
        str(signature) if signature is not None
        else (w.signature if w else "")
    )
    key = (name, kind, signature)
    with _lock:
        count = _seen[key] = _seen.get(key, 0) + 1
    retrace = count > 1
    _compiles.inc(kind=kind)
    if retrace:
        _retraces.inc(kind=kind)
    ev = {
        "ts": time.time(),
        "fn": name,
        "kind": kind,
        "signature": signature,
        "trace_no": count,
        "retrace": retrace,
        "elapsed_s": None,
        "trace_s": None, "lower_s": None, "compile_s": None,
    }
    if w is not None:
        w.events.append(ev)   # elapsed filled at watch exit
    else:
        _emit(ev)


def mark_aot_hit(name, signature="", elapsed_s=None):
    """Record a compiled executable loaded from the persistent compile
    cache (``paddle_tpu.compilecache``) instead of traced. Logged under
    its own ``kind="aot-hit"`` so the event is visible next to compiles
    in postmortems WITHOUT counting as one: it bumps neither
    ``paddle_tpu_jit_compiles_total`` nor the warm-retrace alarm — a
    warm restart that replays its manifest must read as zero compile
    activity."""
    if _suppressed():
        return
    _aot_hits.inc()
    _emit({
        "ts": time.time(),
        "fn": name,
        "kind": "aot-hit",
        "signature": str(signature),
        "trace_no": 0,
        "retrace": False,
        "elapsed_s": elapsed_s,
    })


def aot_hits():
    """Total executables loaded from the persistent compile cache."""
    return sum(v for _, _, v in _aot_hits.family().samples)


def _emit(ev):
    with _lock:
        _log.append(ev)
    from . import flight

    flight.record(
        "compile", ev["fn"], kind=ev["kind"],
        signature=ev["signature"], retrace=ev["retrace"],
        elapsed_s=ev["elapsed_s"],
    )


def compile_log():
    """The bounded compile/retrace event log, oldest first."""
    with _lock:
        return [dict(ev) for ev in _log]


def clear_compile_log():
    """Reset the log and the warmup bookkeeping (tests)."""
    with _lock:
        _log.clear()
        _seen.clear()


def retraces_after_warmup(kind=None):
    """Total retrace-after-warmup count (optionally for one kind)."""
    fam = _retraces.family()
    return sum(
        v for _, labels, v in fam.samples
        if kind is None or labels.get("kind") == kind
    )
