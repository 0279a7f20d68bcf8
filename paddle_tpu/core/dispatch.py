"""Eager op dispatch.

The analogue of the reference's generated `<op>_ad_func` prologue
(fluid/eager/auto_code_generator/generator/eager_gen.py: AMP cast → layout
autotune → dist branch → phi API call → GradNode wiring), collapsed into one
generic dispatcher because VJPs come from jax.vjp instead of generated
GradNode classes.

Pipeline per call:
  1. flatten (Tensor|list[Tensor]|scalar) args, unwrap to jax.Arrays
  2. AMP autocast hook (amp/auto_cast.py registers the active policy)
  3. DistTensor branch: if any input carries a placement, route through the
     distributed dispatcher (spmd rule → reshard → local compute)
  4. run impl; if grad is required, run it under jax.vjp and record a GradNode
  5. optional NaN/Inf scan (FLAGS_check_nan_inf)
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd, flags
from .tensor import Tensor

# Registered by paddle_tpu.amp at import time; None when AMP is off.
_amp_cast_hook: Callable | None = None
# Registered by paddle_tpu.distributed; routes DistTensor inputs.
_dist_dispatch_hook: Callable | None = None
# Installed by jit.graph_break's segment scope: records ops into a lazy
# compiled segment instead of executing them (SOT-fallback mode).
_segment_hook: Callable | None = None
# Installed by profiler while RECORDing: per-op host+device timing
# (block_until_ready inside the timed span — the profiling-overhead
# trade the reference's tracers also make).
_prof_timer: Callable | None = None


def set_amp_hook(fn):
    global _amp_cast_hook
    _amp_cast_hook = fn


def set_dist_hook(fn):
    global _dist_dispatch_hook
    _dist_dispatch_hook = fn


def _is_tensor_leaf(x):
    return isinstance(x, Tensor)


def _tree_flatten_tensors(args):
    """Flatten nested (tuple/list) args, separating Tensor leaves."""
    return jax.tree_util.tree_flatten(
        args, is_leaf=_is_tensor_leaf
    )


# --- eager per-op program cache ------------------------------------------
# The reference makes eager dispatch cheap with ~72k LoC of generated C++
# (eager_gen.py ad_func prologues + cached phi kernels; SURVEY §3.1).
# The TPU-native analogue: cache ONE jitted (out, vjp) program per
# (op, impl, input signature, static attrs) so repeated eager ops skip
# re-tracing jax.vjp — jit's C++ fast path replaces the trace. Entries
# are skipped for tracer inputs (staging must inline, not nest jit) and
# blacklisted for ops that cannot trace (dynamic output shapes).
from collections import OrderedDict as _OrderedDict

ENABLE_OP_CACHE = True  # kill switch (perf A/B, debugging)
_sig_cache: "_OrderedDict[tuple, Any]" = _OrderedDict()
_SIG_CACHE_MAX = 1024
_sig_blacklist: set = set()
# jitted backward applier: the VJP closure is a pytree, so its residual
# arrays are traced args and the transposed program compiles once per
# residual/cotangent signature
_bwd_apply = None


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return ("\x00seq",) + tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return ("\x00map",) + tuple(
            sorted((k, _hashable(x)) for k, x in v.items())
        )
    if isinstance(v, (bool, int, float, complex)):
        # 1 == 1.0 == True hash identically but can change op semantics
        return (type(v).__name__, v)
    hash(v)  # TypeError for unhashables -> caller skips the cache
    return v


def _sig_cache_put(key, entry):
    _sig_cache[key] = entry
    if len(_sig_cache) > _SIG_CACHE_MAX:
        _sig_cache.popitem(last=False)


def clear_op_cache():
    """Drop cached per-op programs (tests / flag toggles)."""
    _sig_cache.clear()
    _sig_blacklist.clear()


def _nan_inf_report(bad, name, level):
    """Host-side reaction to a detected NaN/Inf (shared by the eager and
    staged paths)."""
    if bad:
        msg = f"NaN/Inf detected in output of op '{name}'"
        if level >= 3:
            print(f"[check_nan_inf] {msg}")
        else:
            raise FloatingPointError(msg)


# Active NaN-flag collector: installed by jit.StaticFunction/TrainStep
# while tracing so per-op isfinite reductions become explicit program
# OUTPUTS (checked by the host wrapper after execution). Pure dataflow —
# needs no host-callback support from the backend.
_nan_collector: list | None = None


def set_nan_collector(collector):
    """Install (or clear, with None) the staged NaN-flag collector.
    Returns the previous collector for restoration."""
    global _nan_collector
    prev = _nan_collector
    _nan_collector = collector
    return prev


def _check_nan_inf(name, arrays):
    """ref: fluid/framework/new_executor/nan_inf_utils.cc — the
    reference's check runs in BOTH its eager and static executors. Three
    paths here: concrete arrays check immediately (eager); tracers under
    an installed collector record (op_name, bad_flag) pairs that the
    staging wrapper returns as program outputs (TrainStep/StaticFunction);
    tracers outside any collector (user's own jax.jit) fall back to a
    host debug callback where the backend supports one."""
    level = flags.get_flag("FLAGS_check_nan_inf_level")
    for a in arrays:
        if jnp.issubdtype(a.dtype, jnp.floating):
            bad = jnp.logical_not(jnp.all(jnp.isfinite(a)))
            if isinstance(bad, jax.core.Tracer):
                if _nan_collector is not None:
                    _nan_collector.append((name, bad))
                else:
                    jax.debug.callback(
                        lambda b, _n=name, _l=level: _nan_inf_report(
                            bool(b), _n, _l
                        ),
                        bad,
                    )
            else:
                _nan_inf_report(bool(bad), name, level)


def call(op_name: str, impl: Callable, args: tuple, attrs: dict[str, Any]):
    """Dispatch one op eagerly. `args` may contain Tensors, lists of Tensors,
    and None; `attrs` are static python values closed over the impl."""
    if _segment_hook is not None:
        return _segment_hook(op_name, impl, args, attrs)

    if _amp_cast_hook is not None:
        args = _amp_cast_hook(op_name, args)

    flat, treedef = _tree_flatten_tensors(args)
    tensor_idx = [i for i, x in enumerate(flat) if isinstance(x, Tensor)]

    if _dist_dispatch_hook is not None and any(
        isinstance(flat[i], Tensor) and flat[i].is_dist() for i in tensor_idx
    ):
        return _dist_dispatch_hook(op_name, impl, args, attrs)

    in_tensors = [flat[i] for i in tensor_idx]
    primals = tuple(t._data for t in in_tensors)

    requires_grad = autograd.is_grad_enabled() and any(
        (not t.stop_gradient) for t in in_tensors
    )

    # template with tensor slots blanked: the op closure must NOT hold
    # this call's input Tensors (cached programs would pin their buffers)
    tset = set(tensor_idx)
    template = tuple(
        None if i in tset else x for i, x in enumerate(flat)
    )

    def fn(*arrays):
        rebuilt = list(template)
        for i, a in zip(tensor_idx, arrays):
            rebuilt[i] = a
        rebuilt_args = jax.tree_util.tree_unflatten(treedef, rebuilt)
        return impl(*rebuilt_args, **attrs)

    # cached-program fast path: concrete inputs only (tracers must inline
    # into the enclosing trace — nesting jit would block fusion there)
    # and stable module-level impls only (per-call closures like
    # jit_program / recompute / grad_op would retrace every call)
    cache_key = None
    if (
        ENABLE_OP_CACHE
        and getattr(impl, "__closure__", True) is None
        and getattr(impl, "__module__", "").startswith("paddle_tpu.ops")
        and not any(isinstance(a, jax.core.Tracer) for a in primals)
    ):
        try:
            cache_key = (
                op_name, impl, treedef, requires_grad,
                tuple(tensor_idx),
                tuple(
                    (a.shape, str(a.dtype),
                     bool(getattr(a, "weak_type", False)))
                    for a in primals
                ),
                _hashable(tuple(x for x in template if x is not None)),
                _hashable(attrs),
            )
        except TypeError:
            cache_key = None
        if cache_key is not None and cache_key in _sig_blacklist:
            cache_key = None

    timer = _prof_timer  # capture: stop() on another thread may clear it
    t_prof = None
    if timer is not None:
        import time as _time

        t_prof = _time.perf_counter()
    cached_prog = False
    if cache_key is not None:
        entry = _sig_cache.get(cache_key)
        if entry is None:
            try:
                if requires_grad:
                    entry = jax.jit(lambda *p: jax.vjp(fn, *p))
                else:
                    entry = jax.jit(fn)
                # compile probe BEFORE caching: unjittable ops
                # (dynamic output shapes etc.) fall back for good
                result0 = entry(*primals)
                _sig_cache_put(cache_key, entry)
            except Exception:
                _sig_blacklist.add(cache_key)
                cache_key = None
        else:
            # proven entry: a runtime failure here (OOM, bad values) is
            # a REAL error — surface it; blacklisting would silently
            # drop the op to the slow path for the process lifetime
            _sig_cache.move_to_end(cache_key)
            result0 = entry(*primals)
        if cache_key is not None:
            if requires_grad:
                out, vjp_fn = result0
            else:
                out, vjp_fn = result0, None
            cached_prog = True
    if cache_key is None:
        if requires_grad:
            out, vjp_fn = jax.vjp(fn, *primals)
        else:
            out = fn(*primals)
            vjp_fn = None
    if t_prof is not None:
        try:
            jax.block_until_ready(out)
        except Exception:
            # analysis: allow(broad-except) tracers under an outer jit
            # cannot block; profiler falls back to host time only
            pass
        timer(op_name, _time.perf_counter() - t_prof)

    out_flat, out_treedef = jax.tree_util.tree_flatten(out)
    # float0 leaves (cotangents of integral inputs, from grad-of-grad ops)
    # carry no information — surface them as None.
    out_flat = [
        None
        if (isinstance(a, np.ndarray) and a.dtype == jax.dtypes.float0)
        else a
        for a in out_flat
    ]

    if flags.get_flag("FLAGS_check_nan_inf"):
        _check_nan_inf(op_name, [a for a in out_flat if a is not None])

    # Only float/complex outputs participate in AD; an op whose outputs are
    # all integral (argmax, equal, ...) records nothing.
    def _is_diff(a):
        return a is not None and (
            jnp.issubdtype(a.dtype, jnp.floating)
            or jnp.issubdtype(a.dtype, jnp.complexfloating)
        )

    if requires_grad and any(_is_diff(a) for a in out_flat):
        node = autograd.GradNode(
            op_name,
            vjp_fn,
            tuple(in_tensors),
            len(out_flat),
            out_treedef,
        )
        node.fwd_fn = fn
        node._cached_vjp = cached_prog
        node.out_avals = [
            (a.shape, a.dtype) if a is not None else ((), jnp.float32)
            for a in out_flat
        ]
        out_tensors = [
            Tensor(a, stop_gradient=False, _grad_node=node, _out_index=i)
            if _is_diff(a)
            else (Tensor(a, stop_gradient=True) if a is not None else None)
            for i, a in enumerate(out_flat)
        ]
    else:
        out_tensors = [
            Tensor(a, stop_gradient=True) if a is not None else None
            for a in out_flat
        ]

    result = jax.tree_util.tree_unflatten(out_treedef, out_tensors)
    return result


def _synth_cotangents(node, cotangents):
    """Full cotangent list: missing entries become zeros (float) or float0
    (integral outputs, which jax.vjp requires)."""
    cot_arrays = []
    for (shape, dtype), c in zip(node.out_avals, cotangents):
        if c is not None:
            a = c._data if isinstance(c, Tensor) else c
            if a.dtype != dtype and jnp.issubdtype(dtype, jnp.floating):
                a = a.astype(dtype)
            cot_arrays.append(a)
        elif jnp.issubdtype(dtype, jnp.floating) or jnp.issubdtype(
            dtype, jnp.complexfloating
        ):
            cot_arrays.append(jnp.zeros(shape, dtype))
        else:
            cot_arrays.append(np.zeros(shape, jax.dtypes.float0))
    return cot_arrays


def _wrap_in_cots(node, in_cots):
    result = []
    for t, g in zip(node.inputs, in_cots):
        if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
            result.append(None)
        elif isinstance(g, Tensor):
            result.append(g)
        else:
            result.append(Tensor(g, stop_gradient=True))
    return result


def call_vjp(node, cotangents, create_graph=False):
    """Run a node's vjp under the ``autograd.scope`` path it was recorded
    in, so that a device trace files the backward with its forward."""
    if node.scope:
        with autograd.scope(*node.scope):
            return _call_vjp(node, cotangents, create_graph)
    return _call_vjp(node, cotangents, create_graph)


def _call_vjp(node, cotangents, create_graph):
    """`cotangents`: list (len n_outputs) of Tensor|None.

    Fast path uses the residual closure captured at forward time. The
    create_graph path instead re-runs jax.vjp *through the dispatcher* with
    the original forward inputs as op inputs — that is what connects the
    produced gradients back to the tape for higher-order AD (the reference
    gets this from generated double_grad nodes, backward.yaml *_double_grad).
    """
    if node.vjp_fn is None and node.fwd_fn is None:
        raise RuntimeError(
            f"trying to backward through `{node.name}` a second time after its "
            "graph was freed; call backward(retain_graph=True) the first time"
        )
    if create_graph:
        fwd_fn = node.fwd_fn
        out_treedef = node.out_treedef
        n_in = len(node.inputs)

        def grad_op(*args):
            primal_arrays, cot_arrays = args[:n_in], args[n_in:]
            _, vjp_fn = jax.vjp(fwd_fn, *primal_arrays)
            ct = jax.tree_util.tree_unflatten(out_treedef, list(cot_arrays))
            return tuple(vjp_fn(ct))

        cot_args = []
        for (shape, dtype), c in zip(node.out_avals, cotangents):
            if isinstance(c, Tensor):
                cot_args.append(c)
            else:
                arrs = _synth_cotangents(node, cotangents)
                break
        else:
            arrs = None
        if arrs is not None:
            cot_args = [
                c if isinstance(c, Tensor) else a
                for c, a in zip(cotangents, arrs)
            ]
        outs = call(
            f"{node.name}_grad", grad_op, tuple(node.inputs) + tuple(cot_args), {}
        )
        outs = list(outs) if isinstance(outs, (tuple, list)) else [outs]
        return _wrap_in_cots(node, outs)

    cot_arrays = _synth_cotangents(node, cotangents)
    cot_tree = jax.tree_util.tree_unflatten(node.out_treedef, cot_arrays)
    if node.vjp_fn is None:
        # Graph was partially freed but fwd_fn retained: recompute.
        _, vjp_fn = jax.vjp(node.fwd_fn, *(t._data for t in node.inputs))
    else:
        vjp_fn = node.vjp_fn
    # compiled backward for cache-path nodes: the VJP closure is a
    # pytree, so its residuals become traced args and the transposed
    # program compiles once per signature (float0 cots and tracers take
    # the direct interpreted path)
    if getattr(node, "_cached_vjp", False) and not any(
        isinstance(a, jax.core.Tracer)
        or (isinstance(a, np.ndarray) and a.dtype == jax.dtypes.float0)
        for a in cot_arrays
    ):
        global _bwd_apply
        if _bwd_apply is None:
            _bwd_apply = jax.jit(lambda v, ct: v(ct))
        try:
            in_cots = _bwd_apply(vjp_fn, cot_tree)
        except Exception:
            in_cots = vjp_fn(cot_tree)
    else:
        in_cots = vjp_fn(cot_tree)
    return _wrap_in_cots(node, in_cots)
