"""Tracing harness: turn a function into a closed jaxpr WITHOUT executing.

Two entry shapes, auto-detected by ``trace``:

  * paddle path — ``fn`` is a ``jit.StaticFunction`` (or a Layer forward
    wrapped by one) or takes ``Tensor`` arguments: parameters/buffers are
    lifted to inputs exactly like ``jit.api._build_core`` (so weights do
    NOT show up as baked constants) and ops flow through the normal
    ``core.dispatch`` machinery onto tracers.
  * plain path — ``fn`` is a raw jax-array function (e.g. the serving
    decode step): traced directly with ``jax.make_jaxpr``.

Host-sync points (``bool()``/``.item()``/``np.asarray`` on traced
values) ABORT a jax trace with the graph-break error family
(``jit.graph_break.BREAK_ERRORS``); the harness catches them and returns
the break location as a structured host-sync finding instead of
propagating, so ``analysis.check`` reports the first host sync with
provenance rather than crashing. Analysis is trace-only: nothing is
compiled and nothing executes on device.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field

import jax

from .findings import Finding, Severity

__all__ = ["TraceResult", "trace", "frame_of_eqn", "fn_location"]

_SKIP_DIRS = (
    os.sep + "jax" + os.sep,
    os.sep + "jaxlib" + os.sep,
    os.sep + "jax_graft" + os.sep,
)
_SELF_DIR = os.path.dirname(os.path.abspath(__file__)) + os.sep


def _is_internal_frame(file_name):
    if not file_name or file_name.startswith("<"):
        return True
    if any(d in file_name for d in _SKIP_DIRS):
        return True
    return os.path.abspath(file_name).startswith(_SELF_DIR)


def frame_of_eqn(eqn, prefer_file=None):
    """(file, line) provenance for one jaxpr equation. Prefers the
    innermost frame in ``prefer_file`` (the analyzed function's source),
    falling back to the innermost non-jax frame — for ops routed through
    ``core.dispatch`` that is the op impl, still a real location."""
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return None, None
    fallback = None
    for fr in tb.frames:  # innermost first
        name = fr.file_name
        if _is_internal_frame(name):
            continue
        if prefer_file and os.path.abspath(name) == prefer_file:
            return name, fr.line_num
        if fallback is None:
            fallback = (name, fr.line_num)
    return fallback if fallback is not None else (None, None)


def fn_location(fn):
    """(file, line) of a callable's definition (closure/const findings
    anchor here when no equation carries better provenance)."""
    code = getattr(fn, "__code__", None)
    if code is None:
        code = getattr(getattr(fn, "__func__", None), "__code__", None)
    if code is None or code.co_filename.startswith("<"):
        return None, None
    return code.co_filename, code.co_firstlineno


def resolve(fn):
    """Innermost analyzable callable behind the jit wrapper zoo."""
    seen = set()
    while id(fn) not in seen:
        seen.add(id(fn))
        from ..jit.bucketing import BucketedFunction
        from ..jit.graph_break import GraphBreakFunction

        if isinstance(fn, BucketedFunction):
            fn = fn._fn
        elif isinstance(fn, GraphBreakFunction):
            fn = fn._static
        else:
            break
    return fn


@dataclass
class TraceResult:
    """Everything the passes need: the closed jaxpr (None when tracing
    broke on a host sync), the innermost python function, argument
    bookkeeping for donation checks, and the break finding if any."""

    closed: object = None          # jax.extend.core.ClosedJaxpr | None
    fn: object = None              # innermost callable
    fn_file: str | None = None
    fn_line: int | None = None
    break_finding: Finding | None = None
    # plain path only: flat arg leaves as (argnum, leaf) and, parallel to
    # jaxpr.invars, the argnum each invar came from
    arg_leaves: list = field(default_factory=list)
    invar_argnums: list = field(default_factory=list)
    donate_argnums: tuple = ()

    @property
    def prefer_file(self):
        return os.path.abspath(self.fn_file) if self.fn_file else None


def _break_finding(exc, prefer_file):
    """Locate the host-sync point from a graph-break traceback: the
    innermost frame in the analyzed file (the user line that coerced a
    tracer), else the outermost non-internal frame (the entry into
    whatever library performed the coercion)."""
    file, line = None, None
    fallback = None
    tb = exc.__traceback__
    while tb is not None:  # outermost first
        name = tb.tb_frame.f_code.co_filename
        if not _is_internal_frame(name):
            if prefer_file is not None and (
                os.path.abspath(name) == prefer_file
            ):
                file, line = name, tb.tb_lineno
            elif fallback is None:
                fallback = (name, tb.tb_lineno)
        tb = tb.tb_next
    if file is None and fallback is not None:
        file, line = fallback
    kind = type(exc).__name__
    return Finding(
        rule="host-sync",
        severity=Severity.ERROR,
        message=(
            f"traced value forced to the host ({kind}): bool()/.item()/"
            "np.asarray on a tracer breaks the graph here; keep the "
            "branch in dataflow (lax.cond/where) or hoist it out of the "
            "traced region"
        ),
        file=file,
        line=line,
    )


def _is_tensorish(x):
    from ..core.tensor import Tensor

    return isinstance(x, Tensor)


def _trace_paddle(fn, args, kwargs):
    """Trace a Tensor-level function (optionally a StaticFunction with
    lifted params/buffers) to a closed jaxpr."""
    from ..core import autograd
    from ..core.tensor import Tensor
    from ..jit.api import StaticFunction, _rng_lift, _swap_payloads

    target = fn
    params, buffers = [], []
    if isinstance(fn, StaticFunction):
        params = fn._params
        buffers = fn._buffers
        target = fn._function

    flat, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=_is_tensorish
    )
    # EXACTLY StaticFunction._is_data: what real staging treats as a
    # traced slot. A looser predicate (e.g. hasattr dtype) would trace
    # np scalars the staged program keeps static, producing false
    # host-sync findings for code that stages fine.
    import numpy as np

    def _is_data(x):
        return isinstance(x, (Tensor, jax.Array, np.ndarray))

    slot_set = {i for i, x in enumerate(flat) if _is_data(x)}
    slots = sorted(slot_set)
    arrays = [
        flat[i]._data if isinstance(flat[i], Tensor) else flat[i]
        for i in slots
    ]
    template = [None if i in slot_set else x for i, x in enumerate(flat)]

    def staged(param_arrays, buffer_arrays, key, in_arrays):
        rebuilt = list(template)
        for i, a in zip(slots, in_arrays):
            rebuilt[i] = Tensor(a, stop_gradient=True)
        call_args, call_kwargs = jax.tree_util.tree_unflatten(
            treedef, rebuilt
        )
        old_p = _swap_payloads(params, param_arrays)
        old_b = _swap_payloads(buffers, buffer_arrays)
        try:
            with _rng_lift(key) as lift:
                with autograd.no_grad():
                    out = target(*call_args, **call_kwargs)
                new_key = lift.final_key()
            # read INSIDE the swap window: buffer mutations (BatchNorm
            # running stats) and the advanced RNG key are real outputs
            # of the staged program — without them the update / key-split
            # eqns would read as dead code (false dead-output findings)
            new_buf = [b._data for b in buffers]
        finally:
            _swap_payloads(params, old_p)
            _swap_payloads(buffers, old_b)
        out_flat = jax.tree_util.tree_leaves(
            out, is_leaf=_is_tensorish
        )
        return [
            o._data if isinstance(o, Tensor) else o
            for o in out_flat if _is_data(o)
        ] + new_buf + [new_key]

    key = jax.random.PRNGKey(0)
    closed = jax.make_jaxpr(staged)(
        [p._data for p in params], [b._data for b in buffers], key, arrays
    )
    return closed, target


def trace(fn, args, kwargs, static_argnums=(), donate_argnums=()):
    """Trace ``fn(*args, **kwargs)`` to a ``TraceResult`` (no execution).
    ``static_argnums``/``donate_argnums`` apply to the plain-array path
    (positional args only), mirroring ``jax.jit``'s meaning."""
    from ..jit.api import StaticFunction
    from ..jit.graph_break import BREAK_ERRORS

    fn = resolve(fn)
    paddle_path = isinstance(fn, StaticFunction) or any(
        _is_tensorish(leaf)
        for leaf in jax.tree_util.tree_leaves(
            (args, kwargs), is_leaf=_is_tensorish
        )
    )
    inner = fn._function if isinstance(fn, StaticFunction) else fn
    result = TraceResult(fn=inner, donate_argnums=tuple(donate_argnums))
    result.fn_file, result.fn_line = fn_location(inner)

    try:
        if paddle_path:
            closed, inner = _trace_paddle(fn, args, kwargs)
            result.fn = inner
            result.fn_file, result.fn_line = fn_location(inner)
        else:
            static = set(static_argnums)

            def cache_isolated(*a, **k):
                # fresh function object per trace: jax.make_jaxpr shares
                # the pjit trace cache by function identity, so tracing
                # ``fn`` directly would seed (or consume) the cache of
                # any existing jax.jit(fn) — e.g. the serving decode
                # step's compile-count probe would read 0 after warmup.
                # Passes still inspect ``result.fn`` (the real fn), so
                # source-level checks are not blinded by the wrapper.
                return fn(*a, **k)

            closed = jax.make_jaxpr(
                cache_isolated, static_argnums=tuple(static)
            )(*args, **kwargs)
            argnums = []
            leaves = []
            for i, a in enumerate(args):
                if i in static:
                    continue
                for leaf in jax.tree_util.tree_leaves(a):
                    leaves.append((i, leaf))
                    argnums.append(i)
            for _, v in sorted(kwargs.items()):
                for leaf in jax.tree_util.tree_leaves(v):
                    leaves.append((None, leaf))
                    argnums.append(None)
            result.arg_leaves = leaves
            result.invar_argnums = argnums
    except BREAK_ERRORS as e:
        result.break_finding = _break_finding(e, result.prefer_file)
        return result
    result.closed = closed
    return result
