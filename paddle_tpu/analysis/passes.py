"""Pluggable analysis passes over a closed jaxpr.

Each pass is ``fn(ctx) -> iterable[Finding]`` registered under a stable
rule id. The runner isolates pass failures (an analyzer must never take
down training): a crashing pass becomes a ``pass-crash`` finding by
default, a warning under ``mode="warn"``, and an ``AnalysisError`` only
under ``mode="error"``. Every pass invocation is a fault-injection site
(``analysis.pass``) so the degradation contract is testable with
``resilience.faults``.

Rule catalog (docs/analysis.md has a repro per rule):

    retrace-hazard   Python scalars captured by value in the closure;
                     shape-dependent Python control flow
    dtype-drift      weakly-typed scalar inputs/consts; 64-bit widening
    host-sync        tracer forced to the host (trace break) or host
                     callbacks, escalated inside compiled loops
    const-bloat      large arrays baked into the program as constants
    donation-misuse  donated buffer aliased by another argument, or
                     donated but never consumed
    dead-output      equations whose results are never used
"""
from __future__ import annotations

import ast
import inspect
import textwrap
from dataclasses import dataclass, field

import jax
from jax.extend import core as jex_core

from .findings import AnalysisError, Finding, Severity
from .trace import TraceResult, fn_location, frame_of_eqn

__all__ = ["AnalysisContext", "PASSES", "register_pass", "run_passes"]

# primitives whose body is re-entered per iteration: a host round-trip
# inside one is paid every step, not once
_LOOP_PRIMS = {"scan", "while"}
_CALLBACK_PRIMS = {
    "debug_callback", "pure_callback", "io_callback", "callback",
    "debug_print",
}
_ARITH_PRIMS = {"add", "sub", "mul", "div", "pow", "max", "min"}


@dataclass
class AnalysisContext:
    trace: TraceResult
    const_bloat_bytes: int = 1 << 20

    @property
    def closed(self):
        return self.trace.closed

    @property
    def fn(self):
        return self.trace.fn


def _walk_eqns(jaxpr, in_loop=False):
    """Yield (eqn, in_loop) over a jaxpr and every sub-jaxpr (scan/while
    bodies count as loops; cond branches and pjit bodies do not)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        loop = in_loop or eqn.primitive.name in _LOOP_PRIMS
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub, loop)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for item in v if isinstance(v, (list, tuple)) else (v,):
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


# --- registry ---------------------------------------------------------------
PASSES: dict = {}


def register_pass(name):
    """Register an analysis pass under ``name`` (decorator). Third-party
    passes plug in the same way the built-ins do."""

    def deco(fn):
        PASSES[name] = fn
        return fn

    return deco


# --- built-in passes --------------------------------------------------------
@register_pass("retrace-hazard")
def _retrace_hazard(ctx):
    """(a) Python scalars captured by value: the staged program bakes
    them as constants — updating the Python variable silently does NOT
    retrace. (b) Python control flow on shapes: each distinct shape
    traces a different program (retrace per shape), the hazard
    ``jit.bucketing`` exists to bound."""
    fn = ctx.fn
    raw = inspect.unwrap(getattr(fn, "__func__", fn))
    file, line = fn_location(fn)

    code = getattr(raw, "__code__", None)
    if code is not None and code.co_freevars and raw.__closure__:
        for name, cell in zip(code.co_freevars, raw.__closure__):
            try:
                val = cell.cell_contents
            except ValueError:
                continue  # empty cell
            if isinstance(val, (bool, int, float)):
                yield Finding(
                    rule="retrace-hazard",
                    severity=Severity.WARNING,
                    message=(
                        f"closure captures Python {type(val).__name__} "
                        f"'{name}' by value: it is baked into the traced "
                        "program as a constant and later rebinds do NOT "
                        "retrace; pass it as an argument instead"
                    ),
                    file=file,
                    line=line,
                )

    try:
        src = textwrap.dedent(inspect.getsource(raw))
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError, IndentationError):
        return
    base = (code.co_firstlineno - 1) if code is not None else 0
    for node in ast.walk(tree):
        if not isinstance(node, (ast.If, ast.IfExp, ast.While)):
            continue
        # `if bad_shape: raise ...` is a validation guard, not a branch
        # that multiplies traces — skip raise-only bodies
        if isinstance(node, ast.If) and all(
            isinstance(stmt, ast.Raise) for stmt in node.body
        ):
            continue
        if _mentions_shape(node.test):
            yield Finding(
                rule="retrace-hazard",
                severity=Severity.WARNING,
                message=(
                    "shape-dependent Python control flow: every distinct "
                    "input shape traces (and compiles) a different "
                    "program; pad to buckets (jit.bucketing) or branch "
                    "in dataflow (lax.cond)"
                ),
                file=file,
                line=base + node.test.lineno,
            )


def _mentions_shape(test):
    # Precision over recall: only explicit `.shape` access is matched.
    # `.ndim` is exempt (rank is part of the trace signature anyway, so
    # rank-dispatch like BatchNorm1D's 2D/3D split costs nothing beyond
    # the retrace jit already performs), and bare `len(...)` is exempt
    # (statically indistinguishable from a Python-container length
    # check, an overwhelmingly common and shape-independent branch).
    for sub in ast.walk(test):
        if isinstance(sub, ast.Attribute) and sub.attr == "shape":
            return True
    return False


@register_pass("dtype-drift")
def _dtype_drift(ctx):
    """Weak-type promotion + accidental 64-bit widening. Weakly typed
    scalars (Python numbers passed by value) make downstream dtypes
    follow the scalar instead of the array — the drift the reference
    catches with PIR dtype verification."""
    closed = ctx.closed
    if closed is None:
        return
    file, line = ctx.trace.fn_file, ctx.trace.fn_line
    for kind, vs in (("input", closed.jaxpr.invars),
                     ("closed-over constant", closed.jaxpr.constvars)):
        for v in vs:
            aval = v.aval
            if getattr(aval, "weak_type", False):
                yield Finding(
                    rule="dtype-drift",
                    severity=Severity.WARNING,
                    message=(
                        f"weakly-typed {aval.dtype} {kind} (a Python "
                        "scalar passed by value): promotion downstream "
                        "follows the scalar, so dtypes can silently "
                        "drift; pin the dtype (e.g. jnp.asarray(x, "
                        "dtype=...))"
                    ),
                    file=file,
                    line=line,
                )
    prefer = ctx.trace.prefer_file
    for eqn, _ in _walk_eqns(closed.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = eqn.params.get("new_dtype")
        # 64 bits PER COMPONENT: complex64 (two 32-bit halves) is fine
        if new is not None and (
            (dt := jax.numpy.dtype(new)).itemsize >= (
                16 if dt.kind == "c" else 8
            )
        ):
            f, ln = frame_of_eqn(eqn, prefer)
            yield Finding(
                rule="dtype-drift",
                severity=Severity.WARNING,
                message=(
                    f"widening conversion to {jax.numpy.dtype(new).name}:"
                    " 64-bit compute on TPU is emulated and usually an "
                    "accidental x64 promotion"
                ),
                file=f,
                line=ln,
                op=eqn.primitive.name,
            )


@register_pass("host-sync")
def _host_sync(ctx):
    """Trace breaks (bool()/.item()/np.asarray on a tracer) surfaced by
    the harness, plus host callbacks — escalated inside compiled loops
    where every iteration pays the device->host round-trip."""
    if ctx.trace.break_finding is not None:
        yield ctx.trace.break_finding
    closed = ctx.closed
    if closed is None:
        return
    prefer = ctx.trace.prefer_file
    for eqn, in_loop in _walk_eqns(closed.jaxpr):
        if eqn.primitive.name not in _CALLBACK_PRIMS:
            continue
        f, ln = frame_of_eqn(eqn, prefer)
        if in_loop:
            yield Finding(
                rule="host-sync",
                severity=Severity.WARNING,
                message=(
                    "host callback inside a compiled loop: every "
                    "iteration round-trips to the host, serializing the "
                    "hot loop on PCIe latency"
                ),
                file=f,
                line=ln,
                op=eqn.primitive.name,
            )
        else:
            yield Finding(
                rule="host-sync",
                severity=Severity.INFO,
                message="host callback in the traced program",
                file=f,
                line=ln,
                op=eqn.primitive.name,
            )


@register_pass("const-bloat")
def _const_bloat(ctx):
    """Arrays captured by value bake into the compiled program; big ones
    bloat the executable and dodge donation/sharding."""
    closed = ctx.closed
    if closed is None:
        return
    file, line = ctx.trace.fn_file, ctx.trace.fn_line
    for var in closed.jaxpr.constvars:
        nbytes = var.aval.size * var.aval.dtype.itemsize
        if nbytes >= ctx.const_bloat_bytes:
            yield Finding(
                rule="const-bloat",
                severity=Severity.WARNING,
                message=(
                    f"{nbytes / 1e6:.1f} MB array "
                    f"({var.aval.str_short()}) baked into the program as "
                    "a constant; pass it as an argument so it lives in "
                    "one donatable/shardable buffer"
                ),
                file=file,
                line=line,
            )


@register_pass("donation-misuse")
def _donation_misuse(ctx):
    """A donated buffer is dead after the launch: referencing it through
    another argument position hands XLA two views of one buffer it is
    about to destroy; donating a buffer the program never reads destroys
    it for nothing."""
    tr = ctx.trace
    if not tr.donate_argnums or tr.closed is None:
        return
    file, line = tr.fn_file, tr.fn_line
    donated = set(tr.donate_argnums)
    by_id = {}
    for argnum, leaf in tr.arg_leaves:
        if hasattr(leaf, "dtype"):
            by_id.setdefault(id(leaf), set()).add(argnum)
    for argnums in by_id.values():
        hit = sorted(a for a in argnums & donated if a is not None)
        others = sorted(
            str(a) for a in argnums - donated if a is not None
        )
        if hit and others:
            yield Finding(
                rule="donation-misuse",
                severity=Severity.ERROR,
                message=(
                    f"argument {hit[0]} is donated but the same buffer "
                    f"is also passed as argument {', '.join(others)}: "
                    "after donation the aliased reference points at "
                    "freed memory"
                ),
                file=file,
                line=line,
            )
        elif len(hit) > 1:
            yield Finding(
                rule="donation-misuse",
                severity=Severity.ERROR,
                message=(
                    "the same buffer is donated at argument positions "
                    f"{', '.join(str(a) for a in hit)}: XLA is handed "
                    "two aliases of one buffer it is about to destroy"
                ),
                file=file,
                line=line,
            )
    used = set()
    for eqn, _ in _walk_eqns(tr.closed.jaxpr):
        used.update(
            id(v) for v in eqn.invars if not isinstance(v, jex_core.Literal)
        )
    used.update(
        id(v) for v in tr.closed.jaxpr.outvars
        if not isinstance(v, jex_core.Literal)
    )
    for argnum in sorted(donated):
        invars = [
            v for v, a in zip(tr.closed.jaxpr.invars, tr.invar_argnums)
            if a == argnum
        ]
        if invars and not any(id(v) in used for v in invars):
            yield Finding(
                rule="donation-misuse",
                severity=Severity.WARNING,
                message=(
                    f"argument {argnum} is donated but never consumed "
                    "by the program: its buffer is destroyed for nothing"
                ),
                file=file,
                line=line,
            )


@register_pass("dead-output")
def _dead_output(ctx):
    """Equations whose results reach neither an output nor a live
    equation: computed, shipped through the compiler, thrown away."""
    closed = ctx.closed
    if closed is None:
        return
    jaxpr = closed.jaxpr
    live = {
        id(v) for v in jaxpr.outvars if not isinstance(v, jex_core.Literal)
    }
    prefer = ctx.trace.prefer_file
    dead = []
    for eqn in reversed(jaxpr.eqns):
        if getattr(eqn, "effects", None):
            keep = True  # callbacks etc. are live by effect
        else:
            keep = any(id(v) in live for v in eqn.outvars)
        if keep:
            live.update(
                id(v) for v in eqn.invars
                if not isinstance(v, jex_core.Literal)
            )
        else:
            dead.append(eqn)
    for eqn in reversed(dead):
        f, ln = frame_of_eqn(eqn, prefer)
        yield Finding(
            rule="dead-output",
            severity=Severity.INFO,
            message=(
                f"result of '{eqn.primitive.name}' is never used "
                "(dead computation in the traced program)"
            ),
            file=f,
            line=ln,
            op=eqn.primitive.name,
        )


def run_passes(ctx, mode="collect", passes=None):
    """Run the (selected) passes over ``ctx``, isolating crashes.

    mode="collect": a crashing pass becomes a ``pass-crash`` finding.
    mode="warn":    it degrades to a ``warnings.warn`` — analysis never
                    takes down the caller.
    mode="error":   the failure surfaces as ``AnalysisError``.
    """
    from ..resilience import faults

    findings = []
    if passes is None:
        selected = PASSES
    else:
        unknown = [name for name in passes if name not in PASSES]
        if unknown:
            raise ValueError(
                f"unknown analysis pass(es) {unknown}; registered: "
                f"{sorted(PASSES)}"
            )
        selected = {name: PASSES[name] for name in passes}
    for name, pass_fn in selected.items():
        try:
            faults.fire("analysis.pass", rule=name)
            findings.extend(pass_fn(ctx) or ())
        except Exception as e:
            if mode == "error":
                raise AnalysisError(
                    f"analysis pass '{name}' failed: {e!r}"
                ) from e
            if mode == "warn":
                import warnings

                warnings.warn(
                    f"analysis pass '{name}' failed and was skipped: "
                    f"{e!r}",
                    stacklevel=2,
                )
            else:
                findings.append(Finding(
                    rule="pass-crash",
                    severity=Severity.WARNING,
                    message=f"analysis pass '{name}' crashed: {e!r}",
                ))
    findings.sort(key=lambda f: -int(f.severity))
    return findings
