"""Custom-op registration + runtime-compiled C++ extensions.

ref: python/paddle/utils/cpp_extension (JIT-compiles user C++/CUDA into
a loadable op library) + framework/custom_operator.cc (registration) +
phi/capi (the out-of-tree kernel C ABI).

TPU-native form, two tiers:

* ``register_custom_op(name, impl, vjp=None)`` — register a JAX-traceable
  impl (jnp / lax / **Pallas kernel**) as a first-class framework op: it
  dispatches through core.dispatch (tape, AMP hook, NaN nets, staging all
  apply) and lands in the ``paddle_tpu.ops`` namespace. This is the
  custom-KERNEL path: Pallas is to this framework what hand CUDA is to
  the reference.
* ``load(name, sources)`` — the cpp_extension analogue: compile C++
  sources with the host toolchain (g++ -shared -fPIC) at runtime, bind
  exported functions via ctypes, and wrap them as HOST ops through
  jax.pure_callback (runs on the host with device arrays round-tripped —
  the right tool for CPU-side logic like tokenizers/samplers, not device
  math). The exported C ABI is the simple dense-buffer contract:

      extern "C" void op(const float* in, float* out, int64_t n);
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["register_custom_op", "load", "CustomOpModule"]


def register_custom_op(name, impl, vjp=None, namespace=True):
    """Register ``impl(*arrays, **attrs) -> array(s)`` as op ``name``.

    impl must be jax-traceable (jnp/lax/pallas). ``vjp(primals, cotangent)
    -> input cotangents`` overrides AD when given (otherwise jax.vjp of
    impl serves, which is what you want for jnp/pallas impls that are
    differentiable). The op shows up as paddle_tpu.ops.<name> and runs
    through the standard dispatcher.
    """
    from ..core import dispatch

    vjp_cache: dict = {}

    def _runner(attrs):
        """One custom_vjp instance per attrs set: jax.custom_vjp cannot
        bind keyword attrs, so attrs ride the closure and the instance is
        cached by their repr (stable op identity under jit)."""
        if vjp is None:
            return lambda *arrays: impl(*arrays, **attrs)
        key = repr(sorted(attrs.items()))
        run = vjp_cache.get(key)
        if run is None:
            @jax.custom_vjp
            def run(*arrays):
                return impl(*arrays, **attrs)

            def fwd(*arrays):
                return impl(*arrays, **attrs), arrays

            def bwd(primals, ct):
                return tuple(vjp(primals, ct, **attrs))

            run.defvjp(fwd, bwd)
            vjp_cache[key] = run
        return run

    def api(*args, **attrs):
        return dispatch.call(name, _runner(attrs), args, {})

    api.__name__ = name
    api.__doc__ = f"custom op {name!r} (register_custom_op)"
    if namespace:
        from .. import ops

        setattr(ops, name, api)
        if name not in ops.__all__:
            ops.__all__.append(name)
    return api


_BUILD_CACHE: dict[str, ctypes.CDLL] = {}


def _compile(sources, extra_cflags, build_directory, verbose):
    blobs = []
    for s in sources:
        if os.path.exists(s):
            with open(s) as f:
                blobs.append(f.read())
        else:
            blobs.append(s)  # inline source string
    key = hashlib.sha256(
        "\x00".join(blobs + list(extra_cflags or [])).encode()
    ).hexdigest()[:16]
    if key in _BUILD_CACHE:
        return _BUILD_CACHE[key]
    bdir = build_directory or os.path.join(
        tempfile.gettempdir(), "paddle_tpu_extensions"
    )
    os.makedirs(bdir, exist_ok=True)
    so_path = os.path.join(bdir, f"ext_{key}.so")
    if not os.path.exists(so_path):
        srcs = []
        for i, blob in enumerate(blobs):
            p = os.path.join(bdir, f"ext_{key}_{i}.cc")
            with open(p, "w") as f:
                f.write(blob)
            srcs.append(p)
        # build to a private temp name and publish atomically: concurrent
        # processes (launcher workers, test workers) must never dlopen
        # a half-written .so
        tmp_path = f"{so_path}.{os.getpid()}.tmp"
        cmd = (["g++", "-O2", "-shared", "-fPIC", "-std=c++17"]
               + list(extra_cflags or []) + srcs + ["-o", tmp_path])
        if verbose:
            print("cpp_extension:", " ".join(cmd))
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            raise RuntimeError(
                f"cpp_extension build failed:\n{r.stderr}"
            )
        os.replace(tmp_path, so_path)
    lib = ctypes.CDLL(so_path)
    _BUILD_CACHE[key] = lib
    return lib


class CustomOpModule:
    """Result of load(): exported symbols wrapped as host ops."""

    def __init__(self, lib, functions):
        self._lib = lib
        for fname, spec in functions.items():
            setattr(self, fname, self._make(fname, spec))

    def _make(self, fname, spec):
        cfn = getattr(self._lib, fname)
        cfn.restype = None
        np_dtype = np.dtype(spec.get("dtype", "float32"))
        ctype = np.ctypeslib.ndpointer(dtype=np_dtype, flags="C")
        cfn.argtypes = [ctype, ctype, ctypes.c_int64]

        def host_fn(x):
            x = np.ascontiguousarray(x, dtype=np_dtype)
            out = np.empty_like(x)
            cfn(x, out, x.size)
            return out

        def api(x):
            from ..core import dispatch

            def impl(arr):
                return jax.pure_callback(
                    host_fn,
                    jax.ShapeDtypeStruct(arr.shape, np_dtype),
                    arr,
                    vmap_method="sequential",
                )

            return dispatch.call(f"custom::{fname}", impl, (x,), {})

        api.__name__ = fname
        return api


def load(name, sources, functions=None, extra_cflags=None,
         build_directory=None, verbose=False, **kw):
    """JIT-compile + load a C++ extension (ref cpp_extension.load).

    sources: file paths or inline source strings exporting
    ``extern "C" void fn(const T* in, T* out, int64_t n)`` symbols.
    functions: {symbol: {"dtype": "float32"}} describing each export
    (elementwise dense-buffer ABI). Returns a CustomOpModule whose
    attributes are host ops usable on Tensors (and under jit via
    pure_callback).
    """
    lib = _compile(sources, extra_cflags, build_directory, verbose)
    return CustomOpModule(lib, functions or {})
