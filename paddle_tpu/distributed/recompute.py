"""Activation recomputation (gradient checkpointing).

ref: python/paddle/distributed/fleet/utils/recompute (recompute(),
recompute_sequential) and the static pass
distributed/passes/auto_parallel_recompute.py.

TPU-native: `recompute(fn, *args)` records ONE tape op whose vjp is
`jax.vjp(jax.checkpoint(pure_fn))` — the checkpoint transform drops the
segment's internal residuals and recomputes them in backward, trading
FLOPs for HBM exactly like the reference's RecomputeFunction, but the
recompute schedule is compiled into the XLA program instead of re-running
Python.

Two residuals are kept, where the segment has them: the output and the
log-sum-exp that an attention forward kernel wrote (the flash and MLA
kernels name them ``ATTENTION_OUT`` / ``ATTENTION_LSE`` in their forward
rules). The attention backward needs nothing else beyond q, k and v, which
are cheap projections of the segment's input, so the backward pass does not
run the step's most expensive kernel a second time for two arrays the
forward pass already wrote ([B, T, H d_v] and its row sums a layer). A
segment without such a kernel (or one that reaches attention through the
``jax.numpy`` form) has nothing under those names and keeps nothing.
"""
from __future__ import annotations

import jax

from ..core import autograd, dispatch
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer

__all__ = ["recompute", "recompute_sequential"]


def _layer_state(l):
    return [p for _, p in l.named_parameters()] + [
        b for _, b in l.named_buffers()
    ]


def _callable_state(function):
    """Params/buffers a non-Layer callable depends on: bound Layer
    methods and Layers/Tensors captured in closures or default args."""
    state = []
    seen = set()

    def visit(v):
        if isinstance(v, Layer) and id(v) not in seen:
            seen.add(id(v))
            state.extend(_layer_state(v))
        elif isinstance(v, Tensor) and not v.stop_gradient:
            if id(v) not in seen:
                seen.add(id(v))
                state.append(v)
        elif isinstance(v, (list, tuple)):
            for item in v:
                visit(item)

    visit(getattr(function, "__self__", None))
    for cell in getattr(function, "__closure__", None) or ():
        try:
            visit(cell.cell_contents)
        except ValueError:
            pass
    for d in getattr(function, "__defaults__", None) or ():
        visit(d)
    return state


def recompute(function, *args, use_reentrant=True,
              _extra_state=None, **kwargs):
    """Run `function(*args, **kwargs)` with activation checkpointing.

    Tensor args (and any Layer parameters/buffers the function closes
    over) become inputs of the checkpointed segment so their gradients
    flow; everything computed inside is recomputed during backward instead
    of being saved, but for an attention kernel's output and log-sum-exp
    (the module's docstring)."""
    # Collect params/buffers the function depends on so their gradients
    # flow: Layer instances directly, bound Layer methods, and Layers /
    # Parameters captured in a lambda's closure (the reference pattern
    # recompute(lambda h: self.block(h), h)).
    if isinstance(function, Layer):
        fn = function.forward
        state = _layer_state(function)
    else:
        fn = function
        state = _callable_state(function)
        # dedup against explicit args handled below via identity
        arg_ids = {
            id(a) for a in jax.tree_util.tree_leaves(
                (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)
            )
            if isinstance(a, Tensor)
        }
        state = [t for t in state if id(t) not in arg_ids]
    if _extra_state:
        have = {id(t) for t in state}
        state.extend(t for t in _extra_state if id(t) not in have)

    flat_in, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)
    )
    slots = [i for i, x in enumerate(flat_in) if isinstance(x, Tensor)]
    n_state = len(state)
    out_tree_box = [None]

    # One fresh key per segment, drawn at the OUTER trace level. The
    # forward trace and the checkpoint's backward re-trace both replay
    # from this key (same dropout mask), and the global generator never
    # retains a sub-trace tracer (that leak breaks later ops).
    from ..core import random as random_mod
    from ..kernels.pallas import _compat
    from ..kernels.pallas.flash_attention import ATTENTION_LSE, ATTENTION_OUT

    seg_key = random_mod.split_key()

    def pure(*arrays):
        state_arrays = arrays[:n_state]
        in_arrays = arrays[n_state:]
        old = [t._data for t in state]
        gen = random_mod.default_generator
        saved_key = gen._key
        gen._key = seg_key
        for t, a in zip(state, state_arrays):
            t._data = a
        try:
            rebuilt = list(flat_in)
            for i, a in zip(slots, in_arrays):
                rebuilt[i] = Tensor(a, stop_gradient=True)
            a2, k2 = jax.tree_util.tree_unflatten(treedef, rebuilt)
            with autograd.no_grad(), _compat.recompute_segment():
                out = fn(*a2, **k2)
        finally:
            for t, a in zip(state, old):
                t._data = a
            gen._key = saved_key
        out_flat, out_tree = jax.tree_util.tree_flatten(
            out, is_leaf=lambda x: isinstance(x, Tensor)
        )
        out_tree_box[0] = out_tree
        return tuple(
            o._data if isinstance(o, Tensor) else o for o in out_flat
        )

    ckpt = jax.checkpoint(
        pure, policy=jax.checkpoint_policies.save_only_these_names(
            ATTENTION_OUT, ATTENTION_LSE))
    tensor_inputs = tuple(state) + tuple(flat_in[i] for i in slots)
    results = dispatch.call("recompute", ckpt, tensor_inputs, {})
    results = (
        list(results) if isinstance(results, (tuple, list)) else [results]
    )
    # the out_tree reproduces fn's exact return structure (a single
    # Tensor stays a Tensor; a 1-tuple stays a 1-tuple)
    return jax.tree_util.tree_unflatten(out_tree_box[0], results)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """ref: fleet/utils recompute_sequential — run a Sequential-style
    chain in ctx['segments'] checkpointed chunks (default 1 function per
    segment); kwargs forward to every segment."""
    functions = list(functions)
    segments = int((ctx or {}).get("segments", len(functions))) or len(
        functions
    )
    per = max(1, (len(functions) + segments - 1) // segments)
    out = args
    for i in range(0, len(functions), per):
        chunk = functions[i : i + per]

        def seg_fn(*xs, _chunk=chunk, **kw):
            cur = xs
            for f in _chunk:
                cur = f(*cur, **kw) if kw else f(*cur)
                if not isinstance(cur, tuple):
                    cur = (cur,)
            return cur[0] if len(cur) == 1 else cur

        seg_state = []
        for f in chunk:
            if isinstance(f, Layer):
                seg_state.extend(_layer_state(f))
            else:
                seg_state.extend(_callable_state(f))
        out = recompute(
            seg_fn, *(out if isinstance(out, tuple) else (out,)),
            _extra_state=seg_state, **kwargs
        )
    return out
