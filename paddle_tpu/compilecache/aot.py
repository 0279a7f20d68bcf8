"""AOT executable serialization + cache-key derivation.

The compiled-program analogue of the reference's ahead-of-time executor
pipeline (PAPER.md §1 graph compiler / executors): a ``jax.jit(...)
.lower(...).compile()`` product is serialized through
``jax.experimental.serialize_executable`` (the PJRT executable bytes
plus the pickled in/out pytrees) so a later process can load and run it
with **zero Python tracing and zero XLA compilation** — the body of the
original function never executes again, which is exactly what the
compile-count probes (``EngineMetrics.*_compiles``,
``jit_events.mark_traced``) measure.

Key derivation is content addressing over *(fn name, abstract
signature, environment fingerprint)*: the fingerprint pins the jax /
jaxlib / backend / ``device_kind`` / framework versions, so an upgraded
process — or the same cache directory met by another chip generation —
simply misses (and re-populates) rather than loading an executable
built for a different runtime. The fingerprint is ALSO recorded in each
artifact's metadata and re-checked at load — a copied or hand-edited
artifact whose recorded environment disagrees with the running one is
treated as stale, never executed.

Device binding: a serialized executable is bound to the devices it was
compiled for. Their ids travel in the pickle frame and are handed back
to ``deserialize_and_load(execution_devices=)`` at load; left to its
default, jax loads onto EVERY device of the backend and a one-device
program then fails at execute on a multi-device host.

Serialized artifacts are pickle-based (jax's executable serialization
uses pickle for the pytree defs): a cache directory is TRUSTED INPUT,
the same trust level as the checkpoint directory.
"""
from __future__ import annotations

import hashlib
import io
import os
import pickle

import jax

__all__ = [
    "env_fingerprint", "content_key", "abstractify", "signature_str",
    "serialize_compiled", "deserialize_compiled", "code_fingerprint",
    "AOTUnavailableError",
]

EXEC_FORMAT = "pjrt-exec-pickle-v2"


class AOTUnavailableError(RuntimeError):
    """This jax build cannot serialize compiled executables."""


def _xla_flags_digest():
    """Stable digest of ``XLA_FLAGS``: tokens are whitespace-split and
    sorted, so reordering the same flags never churns the fingerprint —
    but ANY flag change (a different optimization level, an added
    ``--xla_force_host_platform_device_count``) misses the cache
    cleanly instead of replaying an executable compiled under different
    compiler behavior."""
    toks = sorted(
        t for t in os.environ.get("XLA_FLAGS", "").split() if t
    )
    if not toks:
        return "none"
    return hashlib.sha256(" ".join(toks).encode()).hexdigest()[:16]


def env_fingerprint():
    """The version tuple a serialized executable is only valid under."""
    import platform

    import jaxlib

    from .. import __version__ as framework_version

    return {
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "unknown"),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "framework": framework_version,
        "python": platform.python_version(),
        "exec_format": EXEC_FORMAT,
        "xla_flags": _xla_flags_digest(),
    }


def _env_token(env=None):
    env = env or env_fingerprint()
    return "|".join(f"{k}={env[k]}" for k in sorted(env))


def content_key(name, signature, env=None):
    """Content address for one compiled program: sha256 over the fn
    name, its abstract input signature, and the environment
    fingerprint. Hex-truncated to 32 chars (128 bits — collision-safe
    for any plausible cache population)."""
    h = hashlib.sha256()
    h.update(str(name).encode())
    h.update(b"\x00")
    h.update(str(signature).encode())
    h.update(b"\x00")
    h.update(_env_token(env).encode())
    return h.hexdigest()[:32]


def abstractify(tree):
    """Map a pytree of arrays to ShapeDtypeStructs (for ``lower()``
    without materializing inputs)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") else a,
        tree,
    )


def signature_str(tree):
    """Stable abstract-signature string of a pytree of arrays/structs:
    treedef + per-leaf shape/dtype. Hash-friendly and identical across
    processes for identical structures."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    leaves = ",".join(
        f"{tuple(x.shape)}:{x.dtype}" if hasattr(x, "shape") else repr(x)
        for x in flat
    )
    return f"{treedef}|{leaves}"


def code_fingerprint(fn):
    """Stable digest of a python function's bytecode (recursing into
    nested code objects WITHOUT repr()-ing them — reprs embed object
    addresses, which differ across processes). Returns None when the
    callable exposes no code object (builtins, C extensions) — such
    functions are not disk-cacheable.

    Determinism caveat (docs/compilecache.md): the digest covers this
    function's own bytecode, not its callees or closure values — edit a
    helper the cached function calls and the stale executable still
    hits. Bump the cache directory (or remove the artifact) on such
    refactors; the environment fingerprint already catches the common
    invalidators (jax/framework upgrades).
    """
    code = getattr(fn, "__code__", None)
    if code is None:
        code = getattr(getattr(fn, "__func__", None), "__code__", None)
    if code is None:
        return None
    h = hashlib.sha256()

    def feed(c):
        h.update(c.co_code)
        h.update(str(c.co_names).encode())
        h.update(str(c.co_varnames).encode())
        for const in c.co_consts:
            if hasattr(const, "co_code"):
                feed(const)
            elif isinstance(const, frozenset):
                # `x in {...}` literals compile to frozenset constants
                # whose repr order follows PYTHONHASHSEED — hash the
                # sorted elements or the digest differs per process
                h.update(repr(sorted(const, key=repr)).encode())
            else:
                h.update(repr(const).encode())

    h.update(getattr(fn, "__qualname__", str(fn)).encode())
    feed(code)
    return h.hexdigest()[:32]


def serialize_compiled(compiled):
    """``jax.stages.Compiled`` -> bytes (executable payload + pytree
    defs, one pickle frame). Raises :class:`AOTUnavailableError` when
    the backend/jax build does not support executable serialization."""
    try:
        from jax.experimental.serialize_executable import serialize
    except ImportError as e:
        raise AOTUnavailableError(
            "jax.experimental.serialize_executable is unavailable in "
            "this jax build"
        ) from e
    try:
        payload, in_tree, out_tree = serialize(compiled)
    except Exception as e:
        # backends without PJRT executable serialization surface it here
        raise AOTUnavailableError(
            f"backend {jax.default_backend()!r} cannot serialize "
            f"compiled executables: {type(e).__name__}: {e}"
        ) from e
    device_ids = [
        d.id for d in compiled.runtime_executable().local_devices()
    ]
    buf = io.BytesIO()
    pickle.dump((payload, in_tree, out_tree, device_ids), buf,
                protocol=pickle.HIGHEST_PROTOCOL)
    return buf.getvalue()


def deserialize_compiled(data):
    """bytes -> loaded ``jax.stages.Compiled`` (callable with the
    original dynamic arguments; static arguments are baked). Any
    exception here means the blob does not match this runtime — the
    caller treats it as a cache fallback, not an error."""
    from jax.experimental.serialize_executable import (
        deserialize_and_load,
    )

    payload, in_tree, out_tree, device_ids = pickle.loads(data)
    by_id = {d.id: d for d in jax.devices()}
    return deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids],
    )
