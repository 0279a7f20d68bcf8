"""Define-by-run autograd engine.

TPU-native re-design of the reference's eager autograd
(paddle/fluid/eager/: GradNodeBase grad_node_info.h:197, backward engine
backward.cc:105/445, GradTensorHolder accumulation, TensorWrapper saved
inputs). Differences, by design:

  * VJP rules are not hand-generated per op. Each eager op call obtains its
    reverse rule from `jax.vjp` at record time; the returned closure holds the
    residuals on-device (the TensorWrapper analogue). Because jax.Arrays are
    immutable there is no inplace-version hazard to track.
  * The whole tape is jax-traceable Python, so forward+backward+update can be
    staged into a single XLA program by the jit layer.
  * Topological execution mirrors backward.cc: in-degree map + ready queue.
"""
from __future__ import annotations

import contextlib
import threading
from collections import deque

import jax

__all__ = [
    "GradNode",
    "scope",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "set_grad_enabled",
    "run_backward",
    "grad",
]


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True
        self.scope = ()  # names of the enclosing `scope`s, outermost first


_state = _GradState()


def is_grad_enabled() -> bool:
    return _state.enabled


def set_grad_enabled(mode: bool):
    _state.enabled = bool(mode)


class _no_grad(contextlib.ContextDecorator):
    """Context manager AND decorator, like paddle.no_grad."""

    def __init__(self, enabled: bool):
        self._target = enabled
        self._prev_stack = []

    def __enter__(self):
        self._prev_stack.append(_state.enabled)
        _state.enabled = self._target
        return self

    def __exit__(self, *exc):
        _state.enabled = self._prev_stack.pop()
        return False


def no_grad(func=None):
    ctx = _no_grad(False)
    if func is not None:
        return ctx(func)
    return ctx


def enable_grad(func=None):
    ctx = _no_grad(True)
    if func is not None:
        return ctx(func)
    return ctx


@contextlib.contextmanager
def scope(*names):
    """``jax.named_scope`` that the tape remembers. A device trace names an
    operation by the scopes it was traced under, but the tape runs every
    ``vjp_fn`` later, from ``backward()``, outside all of them, and JAX
    keeps only the part of the name stack that lay inside the ``jax.vjp``.
    So a ``GradNode`` records the scope path it was created under and
    ``dispatch.call_vjp`` re-enters it: the forward reads
    ``attention/jvp()/...`` and its backward
    ``attention/transpose(jvp())/...``. Costs one tuple read an op and one
    ``with`` a node, at trace time only under ``jit.TrainStep``."""
    prev = _state.scope
    _state.scope = prev + names
    try:
        with jax.named_scope("/".join(names)):
            yield
    finally:
        _state.scope = prev


class GradNode:
    """One recorded op on the tape.

    `vjp_fn(cotangents_pytree) -> tuple(input cotangents)` — produced by
    jax.vjp at forward time. `inputs` are the forward input Tensors (flat,
    in vjp order); `n_outputs` the number of flat outputs.
    """

    __slots__ = (
        "name",
        "vjp_fn",
        "fwd_fn",
        "inputs",
        "in_edges",
        "n_outputs",
        "out_treedef",
        "out_avals",
        "_out_cotangents",
        "_pending",
        "post_hooks",
        "output_hooks",
        "_cached_vjp",
        "scope",
    )

    def __init__(self, name, vjp_fn, inputs, n_outputs, out_treedef):
        self.name = name
        self.vjp_fn = vjp_fn
        self.fwd_fn = None  # set by dispatch; enables create_graph re-vjp
        self.inputs = inputs  # tuple[Tensor]
        # (producer_node|None, out_index, stop_gradient) captured at record
        # time — robust to later inplace rebinding of the input tensors.
        self.in_edges = tuple((t._grad_node, t._out_index, t.stop_gradient) for t in inputs)
        self.n_outputs = n_outputs
        self.out_treedef = out_treedef
        self.out_avals = []
        self._out_cotangents = None
        self._pending = 0
        self._cached_vjp = False
        self.scope = _state.scope  # re-entered around the vjp
        self.post_hooks = []
        # (out_index, hook) from register_hook on non-leaf outputs; fired
        # on the fully-accumulated output cotangent before the vjp runs
        self.output_hooks = []

    def __repr__(self):
        return f"<GradNode {self.name} n_in={len(self.inputs)} n_out={self.n_outputs}>"


def _accumulate(a, b):
    """Cotangent accumulation (GradTensorHolder analogue) on Tensors."""
    if a is None:
        return b
    from ..ops import api as ops

    return ops.add(a, b)


def _ones_like_tensor(t):
    import jax.numpy as jnp

    from .tensor import Tensor

    return Tensor(jnp.ones_like(t._data), stop_gradient=True)


def _collect_graph(seed_nodes, stop_ids):
    """BFS over producer edges; returns per-node consumer-edge counts.

    Mirrors the in-degree map construction of eager/backward.cc:23. Nodes
    whose every path to the seeds is blocked never run. `stop_ids` are
    tensor ids at which traversal stops (inputs of paddle.grad with
    no-path pruning handled by capture-then-stop).
    """
    pending = {}
    visited = set()
    q = deque(seed_nodes)
    for n in seed_nodes:
        visited.add(id(n))
        pending[id(n)] = pending.get(id(n), 0)
    while q:
        node = q.popleft()
        for t, (p, _, edge_stop) in zip(node.inputs, node.in_edges):
            if edge_stop or id(t) in stop_ids:
                continue
            if p is None:
                continue
            pending[id(p)] = pending.get(id(p), 0) + 1
            if id(p) not in visited:
                visited.add(id(p))
                q.append(p)
    return pending, visited


def run_backward(
    tensors,
    grad_tensors=None,
    retain_graph=False,
    create_graph=False,
    inputs=None,
    accumulate_into_leaves=True,
    allow_unused=False,
):
    """The engine. Returns grads for `inputs` when given (paddle.grad path),
    otherwise writes `.grad` on every reachable leaf (loss.backward path)."""
    from .tensor import Tensor

    tensors = [tensors] if isinstance(tensors, Tensor) else list(tensors)
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    else:
        grad_tensors = (
            [grad_tensors] if isinstance(grad_tensors, Tensor) else list(grad_tensors)
        )
    if len(grad_tensors) != len(tensors):
        raise ValueError(
            f"grad_tensors length {len(grad_tensors)} != tensors length {len(tensors)}"
        )

    input_ids = set()
    captured = {}
    if inputs is not None:
        inputs = [inputs] if isinstance(inputs, Tensor) else list(inputs)
        input_ids = {id(t) for t in inputs}
        captured = {id(t): None for t in inputs}

    # Seed the output cotangents.
    seed_nodes = []
    leaf_seeds = []  # (leaf tensor, seed grad) for roots that are leaves
    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            raise RuntimeError(
                "backward() called on a tensor with stop_gradient=True"
            )
        if g is None:
            if t._data.size != 1:
                raise RuntimeError(
                    "grad must be provided for non-scalar backward roots; "
                    f"got shape {tuple(t.shape)}"
                )
            g = _ones_like_tensor(t)
        node = t._grad_node
        if node is None:
            leaf_seeds.append((t, g))
            continue
        if node._out_cotangents is None:
            node._out_cotangents = [None] * node.n_outputs
            seed_nodes.append(node)
        node._out_cotangents[t._out_index] = _accumulate(
            node._out_cotangents[t._out_index], g
        )

    pending, visited = _collect_graph(seed_nodes, input_ids)
    for n in seed_nodes:
        n._pending = pending.get(id(n), 0)

    def _deposit_leaf(t, g):
        if id(t) in captured or id(t) in input_ids:
            captured[id(t)] = _accumulate(captured.get(id(t)), g)
            return
        if accumulate_into_leaves and t.is_leaf:
            for hook in t._hooks.values():
                out = hook(g)
                if out is not None:
                    g = out
            t.grad = _accumulate(t.grad, g)

    for t, g in leaf_seeds:
        _deposit_leaf(t, g)

    ready = deque(n for n in seed_nodes if n._pending == 0)
    # Nodes with outstanding consumers still in `seed_nodes` order run once
    # their consumers finish; seeds with pending>0 wait like any other node.
    in_flight = {id(n) for n in seed_nodes}

    executed = []
    while ready:
        node = ready.popleft()
        executed.append(node)
        cots = node._out_cotangents
        node._out_cotangents = None
        for out_idx, hook in node.output_hooks:
            g = cots[out_idx]
            if g is not None:
                res = hook(g)
                if res is not None:
                    cots[out_idx] = res
        from . import dispatch

        if create_graph:
            in_cots = dispatch.call_vjp(node, cots, create_graph=True)
        else:
            with no_grad():
                in_cots = dispatch.call_vjp(node, cots, create_graph=False)
        for hook in node.post_hooks:
            hook(node, in_cots)
        if not retain_graph:
            node.vjp_fn = None
        for t, g, (p, out_idx, edge_stop) in zip(
            node.inputs, in_cots, node.in_edges
        ):
            if g is None or edge_stop:
                continue
            if id(t) in captured or id(t) in input_ids:
                captured[id(t)] = _accumulate(captured.get(id(t)), g)
                continue
            if p is None:
                _deposit_leaf(t, g)
                continue
            if id(p) not in visited:
                continue
            if p._out_cotangents is None:
                p._out_cotangents = [None] * p.n_outputs
            p._out_cotangents[out_idx] = _accumulate(
                p._out_cotangents[out_idx], g
            )
            pending[id(p)] -= 1
            if pending[id(p)] == 0 and id(p) not in in_flight:
                in_flight.add(id(p))
                p._pending = 0
                ready.append(p)

    if inputs is not None:
        out = []
        for t in inputs:
            g = captured.get(id(t))
            if g is None and not allow_unused:
                raise RuntimeError(
                    "one of the differentiated tensors appears unused in the "
                    "graph; pass allow_unused=True to return None for it"
                )
            out.append(g)
        return out
    return None


def grad(
    outputs,
    inputs,
    grad_outputs=None,
    retain_graph=None,
    create_graph=False,
    only_inputs=True,
    allow_unused=False,
    no_grad_vars=None,
):
    """paddle.grad analogue (ref: python/paddle/base/dygraph/base.py grad)."""
    if retain_graph is None:
        retain_graph = create_graph
    if no_grad_vars:
        from .tensor import Tensor

        nvs = [no_grad_vars] if isinstance(no_grad_vars, Tensor) else list(no_grad_vars)
        saved = [(t, t.stop_gradient) for t in nvs]
        for t in nvs:
            t.stop_gradient = True
    else:
        saved = []
    try:
        return run_backward(
            outputs,
            grad_tensors=grad_outputs,
            retain_graph=retain_graph,
            create_graph=create_graph,
            inputs=inputs,
            accumulate_into_leaves=False,
            allow_unused=allow_unused,
        )
    finally:
        for t, sg in saved:
            t.stop_gradient = sg
