"""paddle_tpu.compilecache — persistent compile cache + AOT executable
store for second-scale warm restarts.

Every warm signature in this framework — the serving engine's prefill
buckets and decode step, a ``to_static``-staged eval function — costs a
full Python trace plus an XLA compile the first time a process runs it,
and compile-before-first-step is the dominant fixed cost of every bench
row and every fleet replica restart. This package removes it: compiled
executables are serialized to a content-addressed disk store and loaded
back by a later process with zero tracing and zero compilation (the
jaxpr-native analog of the reference's ahead-of-time executor pipeline,
PAPER.md §1 graph compiler / executors / Plan+Jobs).

Three layers (docs/compilecache.md):

  * :class:`store.ArtifactStore` — atomic fsync'd writes, crc32
    verification, ``keep_last_k`` eviction (the checkpoint-v2 write
    discipline applied to executables).
  * :class:`CompileCache` — the facade: content-addressed
    ``load_executable`` / ``store_executable`` keyed on *(fn name,
    abstract signature, jax/backend/framework version)*, with every
    failure mode (corrupt artifact, truncated write, stale version,
    undeserializable blob) degrading to a miss — a broken cache can
    only ever cost a fresh compile, never correctness.
  * :class:`manifest.WarmupManifest` — the per-service trace inventory
    a restarting ``serving.Engine`` replays from disk BEFORE accepting
    traffic.

Wired in at ``EngineConfig(compile_cache=...)`` (serving + fleet
restarts) and ``jit.to_static(cache=...)`` (staged eval functions).
Observability: loads land in the compile/retrace event log as their own
``kind="aot-hit"`` (never tripping the warm-retrace alarm), and a
pull-time collector view exports ``paddle_tpu_compilecache_*`` series
(hits / misses / fallbacks / bytes / load seconds) per cache directory.
"""
from __future__ import annotations

import itertools
import os
import sys
import threading
import time
import weakref

import jax

from .aot import (
    AOTUnavailableError,
    abstractify,
    code_fingerprint,
    content_key,
    deserialize_compiled,
    env_fingerprint,
    serialize_compiled,
    signature_str,
)
from .manifest import WarmupManifest
from .store import ArtifactStore, CacheCorruptError

__all__ = [
    "CompileCache", "CacheMetrics", "ArtifactStore", "WarmupManifest",
    "CacheCorruptError", "AOTUnavailableError", "resolve",
    "cache_root", "enable_persistent_cache",
    "content_key", "env_fingerprint", "signature_str", "abstractify",
    "code_fingerprint", "serialize_compiled", "deserialize_compiled",
]

_EXEC_BLOB = "exec"

_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_root():
    """The one directory everything this framework caches lives under:
    JAX's persistent compilation cache (flat, at the top), the AOT
    executable store a caller places below it
    (``EngineConfig(compile_cache=os.path.join(cache_root(), ...))``)
    and ``io.native``'s built library. ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it, else ``<checkout>/.jax_cache`` — a
    fixed path, because the path is part of what a later process must
    find again."""
    return os.path.abspath(
        os.environ.get(_CACHE_DIR_ENV)
        or os.path.join(_CHECKOUT, ".jax_cache")
    )


def enable_persistent_cache():
    """Point JAX's persistent compilation cache at :func:`cache_root`
    and keep every program, however fast it compiled (the serving
    programs compile in well under jax's default one-second floor).
    Call before the first compile. Where the environment names the
    directory jax has already read it, and nothing is set in code.
    Returns the root."""
    root = cache_root()
    if not os.environ.get(_CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return root

# monotonic ids for metric labels (same rationale as the engine/fleet
# counters: a re-created cache over the same dir must not alias a
# collected one's collector registration)
_cache_counter = itertools.count(1)


class CacheMetrics:
    """Host-side counters for one cache (plain attributes; the registry
    PULLS a snapshot at scrape time through the collector view — the
    same zero-hot-path contract as ``EngineMetrics``)."""

    def __init__(self):
        self.hits = 0            # executables loaded from disk
        self.misses = 0          # absent entries (fresh compile follows)
        self.fallbacks = 0       # corrupt/stale/unloadable -> fresh compile
        self.store_errors = 0    # failed writes (degraded to warnings)
        self.bytes_read = 0
        self.bytes_written = 0
        self.load_seconds = 0.0  # cumulative deserialize+verify time
        self.last_load_ms = 0.0

    def snapshot(self):
        return {
            "hits": self.hits,
            "misses": self.misses,
            "fallbacks": self.fallbacks,
            "store_errors": self.store_errors,
            "bytes_read": self.bytes_read,
            "bytes_written": self.bytes_written,
            "load_seconds": self.load_seconds,
            "last_load_ms": self.last_load_ms,
        }


# metrics attr -> (exported series, kind)
_CACHE_SERIES = {
    "hits": ("paddle_tpu_compilecache_hits_total", "counter"),
    "misses": ("paddle_tpu_compilecache_misses_total", "counter"),
    "fallbacks": ("paddle_tpu_compilecache_fallbacks_total", "counter"),
    "store_errors": (
        "paddle_tpu_compilecache_store_errors_total", "counter",
    ),
    "bytes_read": ("paddle_tpu_compilecache_bytes_read_total", "counter"),
    "bytes_written": (
        "paddle_tpu_compilecache_bytes_written_total", "counter",
    ),
    "load_seconds": (
        "paddle_tpu_compilecache_load_seconds_total", "counter",
    ),
    "last_load_ms": ("paddle_tpu_compilecache_last_load_ms", "gauge"),
}


def _register_view(cache):
    """Pull-time collector over one cache (weakref: a collected cache's
    view unregisters itself — the EngineMetrics pattern)."""
    from ..observability import MetricFamily, get_registry

    ref = weakref.ref(cache)
    label = {"cache": cache.root}

    def collect():
        cc = ref()
        if cc is None:
            return None
        m = cc.metrics
        return [
            MetricFamily(series, kind).add(getattr(m, attr), label)
            for attr, (series, kind) in _CACHE_SERIES.items()
        ]

    get_registry().register_collector(
        f"compilecache.{cache.cache_id}", collect
    )


def _warn(msg):
    sys.stderr.write(f"[compilecache] {msg}\n")


class CompileCache:
    """Disk-backed compile cache over one directory.

        cache = CompileCache("/var/cache/paddle_tpu")
        key = cache.key("serving.decode", signature)
        exe = cache.load_executable(key, name="serving.decode",
                                    signature=signature)
        if exe is None:
            exe = jitted.lower(*abstract_args).compile()
            cache.store_executable(key, exe, name="serving.decode",
                                   signature=signature)

    Failure semantics: ``load_executable`` returns ``None`` for ANY
    problem (absent, corrupt, truncated, stale version, undeserializable)
    — absent counts as a miss, damage counts as a fallback with a logged
    warning and a flight-recorder event; ``store_executable`` returns
    False on failure. Nothing in this class raises on the serving path.
    """

    def __init__(self, path, keep_last_k=None):
        self.root = os.path.abspath(path)
        self.store = ArtifactStore(self.root, keep_last_k=keep_last_k)
        self.env = env_fingerprint()
        self.metrics = CacheMetrics()
        self.cache_id = f"{next(_cache_counter)}"
        self._lock = threading.Lock()
        _register_view(self)

    def __repr__(self):
        return f"CompileCache({self.root!r})"

    # -- keys ----------------------------------------------------------------
    def key(self, name, signature):
        """Content address of one program under THIS environment."""
        return content_key(name, signature, self.env)

    def manifest(self, service_key):
        return WarmupManifest(self.root, service_key)

    # -- load ----------------------------------------------------------------
    def _fallback(self, key, name, reason):
        self.metrics.fallbacks += 1
        _warn(
            f"cache entry for {name!r} ({key}) unusable — falling back "
            f"to a fresh compile: {reason}"
        )
        try:
            from ..observability import flight

            flight.record(
                "compilecache", "fallback", key=key, fn=name,
                reason=reason,
            )
        except Exception:
            # analysis: allow(broad-except) telemetry is best-effort;
            # the fallback-to-compile path must never be blocked by it
            pass

    def _count_hit(self, nbytes, dt):
        with self._lock:
            self.metrics.hits += 1
            self.metrics.bytes_read += nbytes
            self.metrics.load_seconds += dt
            self.metrics.last_load_ms = dt * 1e3

    def fetch(self, key, name="", signature="", _count_hit=True):
        """Verified artifact read: ``(meta, blobs)`` or ``None``.
        Counts a miss when absent; counts a fallback (and warns) when
        present-but-unusable, including a recorded environment that
        disagrees with the running one (a copied or forged artifact
        must never execute under the wrong runtime)."""
        t0 = time.perf_counter()
        try:
            got = self.store.get(key)
        except CacheCorruptError as e:
            self._fallback(key, name, str(e))
            self.store.remove(key)  # unblock the re-store
            return None
        except Exception as e:
            # analysis: allow(broad-except) an injected cc.load fault or
            # a filesystem error IS the scenario this layer degrades:
            # a broken cache may only ever cost a fresh compile
            self._fallback(key, name, f"{type(e).__name__}: {e}")
            return None
        if got is None:
            with self._lock:
                self.metrics.misses += 1
            return None
        meta, blobs = got
        if meta.get("env") != self.env:
            self._fallback(
                key, name,
                f"environment mismatch (artifact: {meta.get('env')!r}, "
                f"running: {self.env!r})",
            )
            return None
        dt = time.perf_counter() - t0
        if _count_hit:
            self._count_hit(sum(len(b) for b in blobs.values()), dt)
        return meta, blobs

    def load_executable_bundle(self, key, name="", signature="",
                               finish=None):
        """Load one serialized executable plus its sidecar blobs:
        ``(exe, meta, blobs)`` or ``None`` on any miss or damage. When
        ``finish(exe, meta, blobs)`` is given its return value replaces
        the triple, and an exception inside it degrades like any other
        damaged artifact — so the hit count and the ``kind="aot-hit"``
        compile-log event (its own kind: neither reads as a compile nor
        trips the warm-retrace alarm) are recorded only once the WHOLE
        bundle, sidecars included, has validated."""
        t0 = time.perf_counter()
        got = self.fetch(key, name=name, signature=signature,
                         _count_hit=False)
        if got is None:
            return None
        meta, blobs = got
        blob = blobs.get(_EXEC_BLOB)
        if blob is None:
            self._fallback(key, name, "artifact holds no executable blob")
            return None
        try:
            exe = deserialize_compiled(blob)
        except Exception as e:
            # analysis: allow(broad-except) any deserialization error
            # (pickle damage, PJRT refusal) means "not loadable here":
            # degrade to a fresh compile, never crash the caller
            self._fallback(
                key, name, f"deserialize failed: {type(e).__name__}: {e}"
            )
            self.store.remove(key)
            return None
        result = (exe, meta, blobs)
        if finish is not None:
            try:
                result = finish(exe, meta, blobs)
            except Exception as e:
                # analysis: allow(broad-except) a damaged sidecar
                # degrades exactly like a damaged executable
                self._fallback(
                    key, name,
                    f"sidecar unusable: {type(e).__name__}: {e}",
                )
                self.store.remove(key)
                return None
        elapsed = time.perf_counter() - t0
        self._count_hit(sum(len(b) for b in blobs.values()), elapsed)
        from ..observability import jit_events

        jit_events.mark_aot_hit(
            name or "<compiled>", signature=signature, elapsed_s=elapsed,
        )
        return result

    def load_meta(self, key):
        """Metadata-only read: the artifact's meta dict or ``None``.
        No blob I/O and no hit/miss accounting — this is the cheap
        side-channel for sidecar metadata (an engine's stored L3
        analysis summary on a warm restart), not an executable load."""
        try:
            meta = self.store.get_meta(key)
        except Exception:
            # analysis: allow(broad-except) metadata is best-effort —
            # an unreadable meta only costs a re-analysis, never a crash
            return None
        if meta is not None and meta.get("env") != self.env:
            return None
        return meta

    def load_executable(self, key, name="", signature=""):
        """Load one serialized executable; ``None`` on any miss or
        damage (see :meth:`load_executable_bundle`)."""
        got = self.load_executable_bundle(
            key, name=name, signature=signature
        )
        return None if got is None else got[0]

    # -- store ---------------------------------------------------------------
    def store_executable(self, key, compiled, name="", signature="",
                         extra_blobs=None, extra_meta=None):
        """Serialize + publish one compiled executable; False on any
        failure (warned, counted — a cache that cannot write only loses
        warm restarts, it never takes down serving)."""
        try:
            blob = serialize_compiled(compiled)
            blobs = {_EXEC_BLOB: blob}
            if extra_blobs:
                blobs.update(extra_blobs)
            meta = {
                "name": name, "signature": str(signature),
                "env": self.env, "created": time.time(),
            }
            if extra_meta:
                meta.update(extra_meta)
            written = self.store.put(key, blobs, meta)
        except Exception as e:
            # analysis: allow(broad-except) write failures (injected
            # cc.write faults, ENOSPC, unserializable backend) degrade
            # to a warning: the compile already happened, serving runs
            with self._lock:
                self.metrics.store_errors += 1
            _warn(
                f"failed to persist {name!r} ({key}): "
                f"{type(e).__name__}: {e}"
            )
            return False
        with self._lock:
            self.metrics.bytes_written += written
        return True


# path -> CompileCache memo: an engine restart inside one process (the
# fleet supervisor path) reuses the instance, its metrics, and its
# collector view instead of stacking registrations per rebuild
_resolved: dict = {}
_resolve_lock = threading.Lock()


def resolve(obj, keep_last_k=None):
    """Coerce a config value into a CompileCache: None passes through,
    a CompileCache is returned as-is, a path string is memoized per
    absolute path. An explicit ``keep_last_k`` is applied to an
    already-memoized cache too (the latest bound wins — a later caller
    must not silently get unbounded retention)."""
    if obj is None or isinstance(obj, CompileCache):
        return obj
    path = os.path.abspath(os.fspath(obj))
    with _resolve_lock:
        cache = _resolved.get(path)
        if cache is None:
            cache = _resolved[path] = CompileCache(
                path, keep_last_k=keep_last_k
            )
        elif keep_last_k is not None:
            cache.store.keep_last_k = keep_last_k
        return cache
