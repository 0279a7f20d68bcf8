"""Profiler (ref: python/paddle/profiler/profiler.py:358 Profiler, :129
make_scheduler, :227 export_chrome_tracing; utils.py:47 RecordEvent).

TPU-first: the heavy lifting (device tracing, xplane capture) is
jax.profiler — the PJRT runtime's tracer replaces the reference's CUPTI
tracer; host annotations use TraceAnnotation (the RecordEvent analogue).
The reference's scheduler state machine (CLOSED/READY/RECORD/RECORD_AND_
RETURN) and the Profiler/RecordEvent UX are preserved so reference
profiling scripts port unchanged. Traces land in a TensorBoard-compatible
log dir; `export_chrome_tracing` names the same artifact directory (the
xplane files include trace-viewer data).
"""
from __future__ import annotations

import enum
import os
import tempfile
import time

import jax

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
    "make_scheduler", "export_chrome_tracing", "export_protobuf", "load_profiler_result",
]


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """State-machine schedule over step numbers (ref profiler.py:129)."""

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        period = closed + ready + record
        if repeat and step >= repeat * period:
            return ProfilerState.CLOSED
        pos = step % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready callback writing to dir_name (ref profiler.py:227).
    The Profiler reads handler.dir_name BEFORE starting the trace so the
    first recording window already lands in dir_name."""

    def handler(prof):
        return dir_name

    handler.dir_name = dir_name
    return handler


def export_protobuf(dir_name, worker_name=None):
    """on_trace_ready callback for the protobuf exporter (ref
    profiler.py:247 export_protobuf).

    Both exporters produce the same TensorBoard xplane artifact here
    (the PJRT tracer has one output format), but this handler writes to
    a distinct ``protobuf/`` subdirectory of ``dir_name`` — a
    reference-ported script wiring one profiler to export_chrome_tracing
    and another to export_protobuf with the SAME dir no longer has the
    second silently overwrite the first's traces — and says so
    explicitly instead of silently aliasing."""
    import warnings

    sub = os.path.join(dir_name, "protobuf")
    warnings.warn(
        "export_protobuf on TPU emits the same TensorBoard xplane "
        f"artifact as export_chrome_tracing; writing to {sub!r} so the "
        "two exporters never overwrite each other",
        stacklevel=2,
    )
    return export_chrome_tracing(sub, worker_name)


def load_profiler_result(path):
    """Profile artifacts are TensorBoard xplane dirs; open with
    tensorboard rather than in-process."""
    return path


# -- op-level statistics (ref profiler_statistic.py) -------------------------
# While a Profiler is in a RECORD state, core.dispatch times every eager
# op (with block_until_ready, so device time lands on the op that spent
# it — the profiling-overhead trade the reference's tracers make too) and
# RecordEvent ranges accumulate here; Profiler.summary() renders the
# aggregated table.

_op_stats: dict | None = None


def _stats_active():
    return _op_stats is not None


def _record_span(name, seconds, category="op"):
    if _op_stats is None:
        return
    key = (category, name)
    ent = _op_stats.get(key)
    if ent is None:
        _op_stats[key] = [1, seconds, seconds, seconds]
    else:
        ent[0] += 1
        ent[1] += seconds
        ent[2] = min(ent[2], seconds)
        ent[3] = max(ent[3], seconds)


def _set_dispatch_timer(on):
    from ..core import dispatch

    dispatch._prof_timer = _record_span if on else None


class RecordEvent:
    """Host-side named range (ref profiler/utils.py:47). Shows up in the
    trace viewer as a TraceAnnotation span."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._ctx = None
        self.begin_time = None
        self.end_time = None

    def begin(self):
        self.begin_time = time.perf_counter()
        self._ctx = jax.profiler.TraceAnnotation(self.name)
        self._ctx.__enter__()

    def end(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
        self.end_time = time.perf_counter()
        if self.begin_time is not None:
            _record_span(
                self.name, self.end_time - self.begin_time, "user"
            )

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    """ref: profiler.py:358. Usage:

        with profiler.Profiler(targets=[...], scheduler=(2, 5)) as p:
            for step in range(N):
                train_one_step()
                p.step()
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        if scheduler is None:
            self._scheduler = lambda step: (
                ProfilerState.RECORD if step >= 0 else ProfilerState.CLOSED
            )
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self._scheduler = lambda step: (
                ProfilerState.RECORD_AND_RETURN
                if step == end - 1
                else (
                    ProfilerState.RECORD
                    if start <= step < end
                    else ProfilerState.CLOSED
                )
            )
        else:
            self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.current_state = ProfilerState.CLOSED
        self.step_num = 0
        self._tracing = False
        self._export_dir = None
        self._log_dir = None
        self._step_times = []
        self._last_step_t = None

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self.current_state = self._scheduler(self.step_num)
        self._maybe_transition(None, self.current_state)
        self._last_step_t = time.perf_counter()
        return self

    def stop(self):
        global _op_stats
        if self._tracing:
            self._stop_trace()
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)
        if _op_stats is self.__dict__.get("_op_stats"):
            _op_stats = None
            _set_dispatch_timer(False)
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        prev = self.current_state
        self.step_num += 1
        self.current_state = self._scheduler(self.step_num)
        self._maybe_transition(prev, self.current_state)

    def _maybe_transition(self, prev, state):
        global _op_stats
        recording = state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN
        )
        if recording and _op_stats is None:
            # accumulate across this profiler's record windows (repeating
            # schedulers re-enter RECORD; stats must not reset per window)
            _op_stats = self._op_stats = (
                self.__dict__.get("_op_stats") or {}
            )
            _set_dispatch_timer(True)
        elif not recording and _op_stats is self.__dict__.get("_op_stats"):
            _op_stats = None
            _set_dispatch_timer(False)
        if recording and not self._tracing and not self._timer_only:
            self._start_trace()
        elif not recording and self._tracing:
            self._stop_trace()
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)

    def _start_trace(self):
        self._log_dir = (
            self._export_dir
            or getattr(self._on_trace_ready, "dir_name", None)
            or tempfile.mkdtemp(prefix="paddle_tpu_prof_")
        )
        jax.profiler.start_trace(self._log_dir)
        self._tracing = True

    def _stop_trace(self):
        jax.profiler.stop_trace()
        self._tracing = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting ---------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Step timing + the op-level statistic tables
        (ref profiler_statistic.py: Overview + Operator Summary).
        sorted_by: 'total' (default) | 'calls' | 'avg' | 'max'."""
        if not self._step_times and not self.__dict__.get("_op_stats"):
            return "no steps recorded"
        ts = self._step_times
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}[time_unit]
        lines = ["Profiler summary"]
        if ts:
            lines += [
                f"  steps: {len(ts)}",
                f"  avg step: {sum(ts) / len(ts) * unit:.3f}{time_unit}",
                f"  min/max: {min(ts) * unit:.3f}/"
                f"{max(ts) * unit:.3f}{time_unit}",
            ]
        stats = self.__dict__.get("_op_stats") or {}
        if op_detail and stats:
            key_idx = {"total": 1, "calls": 0, "avg": None, "max": 3}
            sk = sorted_by or "total"
            grand = sum(v[1] for v in stats.values()) or 1.0

            def sort_key(item):
                (cat, name), v = item
                if sk == "avg":
                    return -(v[1] / v[0])
                return -v[key_idx.get(sk, 1)]

            for cat, title in (("op", "Operator Summary"),
                               ("user", "UserDefined Summary")):
                rows = [it for it in stats.items() if it[0][0] == cat]
                if not rows:
                    continue
                lines.append(f"  -- {title} " + "-" * 40)
                lines.append(
                    f"  {'name':<28}{'calls':>7}{'total':>12}"
                    f"{'avg':>12}{'max':>12}{'ratio':>8}"
                )
                for (c, name), (calls, tot, mn, mx) in sorted(
                    rows, key=sort_key
                ):
                    lines.append(
                        f"  {name[:27]:<28}{calls:>7}"
                        f"{tot * unit:>11.3f}{time_unit:<1}"
                        f"{tot / calls * unit:>11.3f}{time_unit:<1}"
                        f"{mx * unit:>11.3f}{time_unit:<1}"
                        f"{tot / grand * 100:>7.1f}%"
                    )
        if self._log_dir:
            lines.append(f"  trace dir: {self._log_dir} (tensorboard --logdir)")
        out = "\n".join(lines)
        print(out)
        return out
