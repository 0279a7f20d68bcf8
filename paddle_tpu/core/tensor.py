"""The eager Tensor.

Re-design of the reference's `paddle::Tensor` + `AutogradMeta`
(paddle/phi/api/include/tensor.h:82, fluid/eager/autograd_meta.h:61) for a
PJRT/XLA world: the payload is an immutable `jax.Array` (so views, inplace
version counters, and stream safety all collapse away), autograd metadata
lives directly on the wrapper, and distributed placement is carried as a
(ProcessMesh, placements) pair lowered to a NamedSharding.

Most operator methods (`__add__`, `.matmul`, `.sum`, ...) are patched onto
this class by `paddle_tpu.ops` at import time — the analogue of the
reference's `tensor_patch_methods.py` / `eager_math_op_patch.cc`.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import dtype as dtype_mod
from .device import Place, current_place

# Installed by jit.graph_break while a lazy segment is live: called before
# any concrete read of a Tensor payload, flushing the pending compiled
# segment (the graph-break trigger point).
_lazy_flush_hook = None


def _coerce_array(data, dtype=None):
    if isinstance(data, Tensor):
        arr = data._data
    elif isinstance(data, jax.Array):
        arr = data
    elif isinstance(data, np.ndarray):
        arr = jnp.asarray(data)
    elif isinstance(data, (bool, int, float, complex, list, tuple)):
        np_arr = np.asarray(data)
        if dtype is None and np_arr.dtype == np.float64:
            np_arr = np_arr.astype(
                dtype_mod.default_float_dtype().jnp_dtype
            )
        if dtype is None and np_arr.dtype == np.int64:
            np_arr = np_arr.astype(np.int32)  # TPU-native index dtype
        arr = jnp.asarray(np_arr)
    else:
        arr = jnp.asarray(data)
    if dtype is not None:
        arr = arr.astype(dtype_mod.to_jnp(dtype))
    return arr


class Tensor:
    __slots__ = (
        "_data",
        "stop_gradient",
        "grad",
        "_grad_node",
        "_out_index",
        "_hooks",
        "_hook_next_id",
        "persistable",
        "name",
        "_version",
        "_dist_meta",
        "__weakref__",
    )

    def __init__(
        self,
        data,
        dtype=None,
        place: Place | None = None,
        stop_gradient: bool = True,
        name: str | None = None,
        _grad_node=None,
        _out_index: int = 0,
    ):
        self._data = _coerce_array(data, dtype)
        self.stop_gradient = stop_gradient
        self.grad = None
        self._grad_node = _grad_node
        self._out_index = _out_index
        self._hooks = {}
        self._hook_next_id = 0
        self.persistable = False
        self.name = name
        self._version = 0
        self._dist_meta = None  # (ProcessMesh, placements) when DistTensor

    # -- metadata ----------------------------------------------------------
    @property
    def shape(self):
        if self._dist_meta is not None:
            return list(self._dist_meta.global_shape_of(self._data))
        return list(self._data.shape)

    @property
    def ndim(self):
        if self._dist_meta is not None:
            return len(self._dist_meta.global_shape_of(self._data))
        return self._data.ndim

    dim = ndim

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def dtype(self):
        return dtype_mod.convert_dtype(self._data.dtype)

    @property
    def place(self) -> Place:
        dev = next(iter(self._data.devices()))
        return Place(dev.platform, dev.id)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    def is_dist(self) -> bool:
        return self._dist_meta is not None

    @property
    def process_mesh(self):
        return None if self._dist_meta is None else self._dist_meta.mesh

    @property
    def placements(self):
        return None if self._dist_meta is None else self._dist_meta.placements

    # -- conversion --------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._local_or_global_data())

    def _local_or_global_data(self):
        if _lazy_flush_hook is not None:
            _lazy_flush_hook(self)  # graph-break segment: concretize
        if self._dist_meta is not None:
            from ..distributed import dist_tensor

            return dist_tensor.to_global_array(self)
        return self._data

    def item(self, *args):
        data = self._local_or_global_data()
        if args:
            return (
                data[args].item()
                if len(args) > 1
                else np.asarray(data).flat[args[0]].item()
            )
        return data.item()

    def tolist(self):
        return np.asarray(self._local_or_global_data()).tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of a Tensor with more than one element is ambiguous"
            )
        return bool(self.item())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.shape[0]

    def __hash__(self):
        return id(self)

    # -- autograd ----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        from . import autograd

        autograd.run_backward(
            [self],
            grad_tensors=[grad_tensor] if grad_tensor is not None else None,
            retain_graph=retain_graph,
        )

    def register_hook(self, hook):
        """Fires when this tensor's gradient is fully accumulated (ref:
        fluid/eager/hooks.h GradientHook semantics — leaf hooks fire at
        grad deposit, non-leaf hooks fire on the producer node's output
        cotangent right before it back-propagates)."""
        hook_id = self._hook_next_id
        self._hook_next_id += 1
        self._hooks[hook_id] = hook
        node_entry = None
        if self._grad_node is not None:
            node_entry = (self._out_index, hook)
            self._grad_node.output_hooks.append(node_entry)

        grad_node = self._grad_node

        class _Handle:
            def remove(_self):
                self._hooks.pop(hook_id, None)
                if node_entry is not None and grad_node is not None:
                    try:
                        grad_node.output_hooks.remove(node_entry)
                    except ValueError:
                        pass

        return _Handle()

    def clear_grad(self):
        self.grad = None

    clear_gradient = clear_grad

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True)
        t._dist_meta = self._dist_meta
        t.name = self.name
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self._out_index = 0
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from ..ops import api as ops

        return ops.assign(self)

    @property
    def inplace_version(self):
        return self._version

    def _bump_version(self):
        self._version += 1

    def _rebind(self, array, dist_meta=...):
        """Inplace-op support: rebind payload (jax.Arrays are immutable so
        saved vjp residuals are never corrupted; ref needed TensorWrapper
        version checks, tensor_wrapper.h)."""
        self._data = array
        if dist_meta is not ...:
            self._dist_meta = dist_meta
        self._bump_version()
        return self

    # -- misc API parity ---------------------------------------------------
    def astype(self, dtype):
        from ..ops import api as ops

        return ops.cast(self, dtype)

    def cast(self, dtype):
        return self.astype(dtype)

    def cpu(self):
        return Tensor(
            jax.device_put(self._data, jax.devices("cpu")[0]),
            stop_gradient=self.stop_gradient,
        )

    def to(self, *args, **kwargs):
        device = kwargs.get("device")
        dtype = kwargs.get("dtype")
        for a in args:
            if isinstance(a, str) and (a.startswith(("cpu", "tpu", "gpu")) or ":" in a):
                device = a
            else:
                dtype = a
        out = self
        if dtype is not None:
            out = out.astype(dtype)
        if device is not None:
            from .device import parse_device

            place = parse_device(device)
            out = Tensor(
                jax.device_put(out._data, place.jax_device),
                stop_gradient=out.stop_gradient,
            )
        return out

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    def pin_memory(self):
        return self

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        if self._dist_meta is not None:
            return (
                f"DistTensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"placements={self._dist_meta.placements}{grad_info},\n"
                f"  local={np.asarray(self._data)!r})"
            )
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
            f"place={self.place}{grad_info},\n  {np.asarray(self._data)!r})"
        )

    # Patched-on operator methods arrive from paddle_tpu.ops.tensor_patch.


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """paddle.to_tensor analogue (ref: python/paddle/tensor/creation.py)."""
    t = Tensor(data, dtype=dtype, stop_gradient=stop_gradient)
    if place is not None:
        from .device import parse_device

        if isinstance(place, str):
            place = parse_device(place)
        t = Tensor(
            jax.device_put(t._data, place.jax_device),
            stop_gradient=stop_gradient,
        )
    return t


jax.tree_util.register_pytree_node(
    Tensor,
    lambda t: ((t._data,), (t.stop_gradient, t._dist_meta)),
    lambda aux, children: _tensor_from_pytree(aux, children),
)


def _tensor_from_pytree(aux, children):
    t = Tensor.__new__(Tensor)
    t._data = children[0]
    t.stop_gradient = aux[0]
    t.grad = None
    t._grad_node = None
    t._out_index = 0
    t._hooks = {}
    t._hook_next_id = 0
    t.persistable = False
    t.name = None
    t._version = 0
    t._dist_meta = aux[1]
    return t
