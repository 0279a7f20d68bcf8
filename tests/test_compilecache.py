"""paddle_tpu.compilecache: persistent compile cache + AOT executable
store for second-scale warm restarts.

The acceptance criteria asserted directly on a deterministic CPU suite:

  * a cache-warm ``Engine`` restart replays its warmup manifest from
    disk with ZERO fresh traces (the traced-body compile probes stay
    still) and greedy outputs bit-identical to the cold-compiled run;
  * ``Fleet.rolling_restart`` rebuilds every replica warm — the second
    replica of a shared-cache fleet never compiles at all;
  * every damaged-cache shape — bit-flipped blob, truncated blob,
    stale-version entry, injected ``cc.load``/``cc.write`` faults —
    degrades to a fresh compile with a logged warning and a bumped
    ``compilecache_fallbacks_total`` (or store-error) counter, never a
    crash;
  * ``jit.save(bucket_sizes=)`` exports one program per bucket and
    ``load`` picks/pads/slices by shape; a version-mismatched blob
    raises a clear error naming both jax versions.

Compile-lean: one module-scope tiny Llama, single prefill bucket,
engines sized 2 slots; the failure-path tests damage ONE artifact in a
copied cache directory so only that program recompiles.
"""
import json
import os
import shutil

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import compilecache, jit, nn
from paddle_tpu.compilecache import (
    ArtifactStore,
    CacheCorruptError,
    CompileCache,
    WarmupManifest,
)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import jit_events
from paddle_tpu.resilience import FaultSpec, faults
from paddle_tpu.serving import (
    Engine,
    EngineConfig,
    Fleet,
    FleetConfig,
    SamplingParams,
)

PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [8, 9, 10, 11, 12]]


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _engine_config(cache_dir, **kw):
    base = dict(
        max_batch_slots=2, max_model_len=32, page_size=4,
        prefill_buckets=[32], compile_cache=str(cache_dir),
    )
    base.update(kw)
    return EngineConfig(**base)


def _tokens(engine):
    """Greedy token tuples in submission order (the generate
    contract), the bit-parity comparison unit."""
    outs = engine.generate(PROMPTS, SamplingParams(max_new_tokens=6))
    # a loaded executable that fails at execute comes back as
    # finish_reason="error" with no tokens: it must not read as
    # "identical (empty) output"
    assert all(
        o.finish_reason != "error" and o.token_ids for o in outs
    ), [(o.finish_reason, o.token_ids) for o in outs]
    return [tuple(o.token_ids) for o in outs]


@pytest.fixture(scope="module")
def warm_cache(model, tmp_path_factory):
    """One cold engine build+run: populates a cache directory every
    warm/damage test copies from, so the module pays the full compile
    set exactly once."""
    root = tmp_path_factory.mktemp("cc")
    eng = Engine(model, _engine_config(root))
    cold = _tokens(eng)
    assert eng.metrics.prefill_compiles >= 1
    assert eng.metrics.decode_compiles == 1
    return str(root), cold


def _damaged_copy(src, tmp_path, mutate):
    """Copy the warm cache dir and apply ``mutate(objects_dir, entry)``
    to the DECODE artifact (found via the warmup manifest)."""
    dst = str(tmp_path / "cache")
    shutil.copytree(src, dst)
    mdir = os.path.join(dst, "manifests")
    (mname,) = os.listdir(mdir)
    with open(os.path.join(mdir, mname)) as f:
        entries = json.load(f)["entries"]
    (decode,) = [e for e in entries if e["kind"] == "decode"]
    mutate(os.path.join(dst, "objects"), decode)
    return dst


class TestArtifactStore:
    """Pure-filesystem layer: atomicity, verification, eviction."""

    def test_put_get_roundtrip(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        st.put("k1", {"exec": b"payload"}, {"name": "f"})
        meta, blobs = st.get("k1")
        assert blobs == {"exec": b"payload"}
        assert meta["name"] == "f"
        assert "exec" in meta["checksums"]
        assert st.get("absent") is None

    def test_bit_flip_raises_corrupt(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        st.put("k1", {"exec": b"x" * 64}, {})
        p = tmp_path / "objects" / "k1" / "exec.bin"
        raw = bytearray(p.read_bytes())
        raw[10] ^= 0xFF
        p.write_bytes(bytes(raw))
        with pytest.raises(CacheCorruptError, match="checksum"):
            st.get("k1")

    def test_truncated_blob_raises_corrupt(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        st.put("k1", {"exec": b"x" * 64}, {})
        p = tmp_path / "objects" / "k1" / "exec.bin"
        p.write_bytes(p.read_bytes()[:32])
        with pytest.raises(CacheCorruptError, match="checksum"):
            st.get("k1")

    def test_unreadable_meta_raises_corrupt(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        st.put("k1", {"exec": b"x"}, {})
        (tmp_path / "objects" / "k1" / "meta.json").write_text("{oops")
        with pytest.raises(CacheCorruptError, match="metadata"):
            st.get("k1")

    def test_failed_put_leaves_previous_state(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        st.put("k1", {"exec": b"old"}, {})
        with pytest.raises(TypeError):
            st.put("k1", {"exec": "not-bytes"}, {})
        _, blobs = st.get("k1")
        assert blobs["exec"] == b"old"  # torn write never visible
        assert not [
            n for n in os.listdir(tmp_path) if n.startswith(".tmp-")
        ]

    def test_keep_last_k_eviction(self, tmp_path):
        st = ArtifactStore(str(tmp_path), keep_last_k=2)
        for i in range(4):
            st.put(f"k{i}", {"b": bytes([i])}, {})
            os.utime(st._dir(f"k{i}"), (i, i))  # deterministic order
        st.put("k9", {"b": b"z"}, {})
        keys = set(st.keys())
        assert "k9" in keys and len(keys) == 2

    def test_invalid_keys_rejected(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        for bad in ("", "a/b", ".hidden"):
            with pytest.raises(ValueError):
                st.put(bad, {"b": b""}, {})
        with pytest.raises(ValueError):
            ArtifactStore(str(tmp_path), keep_last_k=0)

    def test_same_key_republish_is_atomic_and_clean(self, tmp_path):
        """Replacing an existing artifact renames the old one aside
        (readers never see the key absent) and leaves no ``.old-*`` /
        ``.tmp-*`` residue once the new artifact has landed."""
        st = ArtifactStore(str(tmp_path))
        st.put("k1", {"exec": b"old"}, {"gen": 1})
        st.put("k1", {"exec": b"new"}, {"gen": 2})
        meta, blobs = st.get("k1")
        assert blobs["exec"] == b"new" and meta["gen"] == 2
        leftovers = [
            n for n in os.listdir(tmp_path)
            if n.startswith((".tmp-", ".old-"))
        ]
        assert leftovers == []

    def test_failed_republish_restores_previous_artifact(
        self, tmp_path, monkeypatch
    ):
        """When the final rename of a re-publish fails, the previous
        artifact (already renamed aside) is put back — a failed publish
        must never LOSE the live entry."""
        st = ArtifactStore(str(tmp_path))
        st.put("k1", {"exec": b"old"}, {"gen": 1})
        final = st._dir("k1")
        real_rename = os.rename

        def flaky(src, dst):
            if dst == final and ".tmp-" in src:
                raise OSError(13, "injected rename failure")
            return real_rename(src, dst)

        monkeypatch.setattr(os, "rename", flaky)
        with pytest.raises(OSError, match="injected rename"):
            st.put("k1", {"exec": b"new"}, {"gen": 2})
        monkeypatch.undo()
        meta, blobs = st.get("k1")
        assert blobs["exec"] == b"old" and meta["gen"] == 1
        assert not [
            n for n in os.listdir(tmp_path)
            if n.startswith((".tmp-", ".old-"))
        ]

    def test_stale_staging_dirs_swept_on_init(self, tmp_path):
        """Crash-orphaned ``.tmp-*``/``.old-*`` dirs are swept at store
        construction once old enough; a young dir (possibly a live
        concurrent writer's) is left alone."""
        for name, age_s in ((".tmp-dead", 7200), (".old-dead", 7200),
                            (".tmp-live", 10)):
            d = tmp_path / name
            d.mkdir()
            t = __import__("time").time() - age_s
            os.utime(d, (t, t))
        ArtifactStore(str(tmp_path))
        left = {
            n for n in os.listdir(tmp_path)
            if n.startswith((".tmp-", ".old-"))
        }
        assert left == {".tmp-live"}


class TestKeysAndManifest:
    def test_content_key_env_sensitivity(self):
        env = compilecache.env_fingerprint()
        k1 = compilecache.content_key("f", "sig", env)
        assert k1 == compilecache.content_key("f", "sig", env)
        assert k1 != compilecache.content_key("g", "sig", env)
        assert k1 != compilecache.content_key("f", "sig2", env)
        stale = dict(env, jax="0.0.1")
        assert k1 != compilecache.content_key("f", "sig", stale)

    def test_code_fingerprint_tracks_bytecode(self):
        def mk(two):
            if two:
                def f(x):
                    return x + 2
            else:
                def f(x):
                    return x + 1
            return f

        # identical code object -> identical digest across INSTANCES
        # (no object addresses leak into the hash)
        assert compilecache.code_fingerprint(mk(False)) == \
            compilecache.code_fingerprint(mk(False))
        assert compilecache.code_fingerprint(mk(False)) != \
            compilecache.code_fingerprint(mk(True))
        assert compilecache.code_fingerprint(len) is None

    def test_frozenset_const_fingerprint_order_insensitive(self):
        """``x in {...}`` literals compile to frozenset constants whose
        iteration (and repr) order varies with PYTHONHASHSEED — the
        digest must sort them or two processes disagree on the key. 1
        and 9 collide in a size-8 set table, so the two build orders
        below iterate differently even within one process."""
        import types

        def base(x):
            return x in {1, 9}

        code = base.__code__

        def with_set(fs):
            consts = tuple(
                fs if isinstance(c, frozenset) else c
                for c in code.co_consts
            )
            return types.FunctionType(
                code.replace(co_consts=consts), {}, "base"
            )

        a, b = frozenset([1, 9]), frozenset([9, 1])
        assert list(a) != list(b)  # the orders genuinely differ
        assert compilecache.code_fingerprint(with_set(a)) == \
            compilecache.code_fingerprint(with_set(b))

    def test_manifest_roundtrip(self, tmp_path):
        m = WarmupManifest(str(tmp_path), "svc")
        m.add("f", "sig", "key1", kind="decode")
        m.add("f", "sig", "key1", kind="decode")  # idempotent
        m.add("g", "sig2", "key2", kind="prefill", bucket=32)
        m.save()
        m2 = WarmupManifest(str(tmp_path), "svc")
        assert m2.load() == m.entries
        assert len(m.entries) == 2

    def test_resolve_memoizes_and_rebinds_keep_last_k(self, tmp_path):
        p = str(tmp_path / "cc")
        c1 = compilecache.resolve(p)
        assert compilecache.resolve(p) is c1
        assert c1.store.keep_last_k is None
        c2 = compilecache.resolve(p, keep_last_k=2)
        assert c2 is c1 and c1.store.keep_last_k == 2

    def test_manifest_damage_degrades_to_empty(self, tmp_path):
        m = WarmupManifest(str(tmp_path), "svc")
        assert m.load() == []  # absent
        os.makedirs(tmp_path / "manifests", exist_ok=True)
        (tmp_path / "manifests" / "svc.json").write_text("{torn")
        assert m.load() == []


class TestXlaFlagsFingerprint:
    def test_flag_flip_misses_cleanly(self, tmp_path, monkeypatch,
                                      capsys):
        """XLA flags change compiler behavior without touching any
        version number — they must fold into the environment
        fingerprint. Flipping ``XLA_FLAGS`` re-keys the same (fn,
        signature) (clean miss); reordering the SAME flags does not
        churn the digest; and a force-fetch of an artifact recorded
        under the old flags is a counted fallback, never a hit."""
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        base = compilecache.env_fingerprint()
        assert base["xla_flags"] == "none"
        cc = CompileCache(str(tmp_path))
        key = cc.key("f", "sig")
        cc.store.put(
            key, {"exec": b"payload"}, {"name": "f", "env": cc.env}
        )

        monkeypatch.setenv(
            "XLA_FLAGS",
            "--xla_cpu_multi_thread_eigen=false --xla_foo_bar=3",
        )
        flipped = compilecache.env_fingerprint()
        assert flipped["xla_flags"] not in ("none", base["xla_flags"])
        cc2 = CompileCache(str(tmp_path))  # re-reads the environment
        assert cc2.key("f", "sig") != key  # clean miss by key

        # same flags, different token order: identical fingerprint
        monkeypatch.setenv(
            "XLA_FLAGS",
            "--xla_foo_bar=3   --xla_cpu_multi_thread_eigen=false",
        )
        assert compilecache.env_fingerprint() == flipped

        # even fetching the OLD key directly degrades: the recorded env
        # disagrees with the running one -> fallback, never a hit
        cc3 = CompileCache(str(tmp_path))
        assert cc3.fetch(key, name="f") is None
        snap = cc3.metrics.snapshot()
        assert snap["fallbacks"] == 1 and snap["hits"] == 0
        assert "environment mismatch" in capsys.readouterr().err


class TestCacheAccounting:
    """Hit accounting is deferred until the WHOLE bundle validates: a
    fetched-but-unusable artifact is one fallback, never a hit — so
    ``hits`` counts only loads that actually replaced a compile."""

    def test_undeserializable_blob_is_fallback_not_hit(
        self, tmp_path, capsys
    ):
        cc = CompileCache(str(tmp_path))
        key = cc.key("f", "sig")
        # valid store entry (crc passes, env matches) whose executable
        # payload is garbage — deserialize is the failing stage
        cc.store.put(
            key, {"exec": b"not-a-pickled-executable"},
            {"name": "f", "env": cc.env},
        )
        hits0 = jit_events.aot_hits()
        assert cc.load_executable(key, name="f") is None
        snap = cc.metrics.snapshot()
        assert snap["hits"] == 0 and snap["fallbacks"] == 1
        assert jit_events.aot_hits() == hits0  # no aot-hit event either
        assert "deserialize failed" in capsys.readouterr().err
        assert not cc.store.contains(key)  # bad entry dropped

    def test_sidecar_failure_is_fallback_not_hit(self, tmp_path, capsys):
        import jax

        cc = CompileCache(str(tmp_path))
        key = cc.key("g", "sig")
        compiled = jax.jit(lambda x: x + 1).lower(
            jax.ShapeDtypeStruct((2,), "float32")
        ).compile()
        assert cc.store_executable(
            key, compiled, name="g",
            extra_blobs={"out_tree": b"torn-sidecar"},
        )
        hits0 = jit_events.aot_hits()

        def finish(exe, meta, blobs):
            raise ValueError(f"bad sidecar: {blobs['out_tree'][:4]!r}")

        got = cc.load_executable_bundle(key, name="g", finish=finish)
        assert got is None
        snap = cc.metrics.snapshot()
        assert snap["hits"] == 0 and snap["fallbacks"] == 1
        assert jit_events.aot_hits() == hits0
        assert "sidecar unusable" in capsys.readouterr().err
        assert not cc.store.contains(key)
        # and the healthy bundle DOES hit, exactly once, finish applied
        key2 = cc.key("g2", "sig")
        cc.store_executable(key2, compiled, name="g2")
        got = cc.load_executable_bundle(
            key2, name="g2", finish=lambda exe, meta, blobs: exe
        )
        assert got is not None
        assert cc.metrics.hits == 1
        assert jit_events.aot_hits() == hits0 + 1


class TestEngineWarmRestart:
    """The headline acceptance test: kill -> rebuild with a warm cache
    replays the manifest from disk with zero fresh traces and
    bit-identical greedy outputs."""

    def test_warm_restart_zero_traces_bit_identical(
        self, model, warm_cache
    ):
        root, cold = warm_cache
        hits0 = jit_events.aot_hits()
        eng = Engine(model, _engine_config(root))
        # zero fresh traces: the compile probes live INSIDE the traced
        # bodies, so they move only when XLA actually retraces
        assert eng.metrics.prefill_compiles == 0
        assert eng.metrics.decode_compiles == 0
        assert jit_events.aot_hits() >= hits0 + 2
        assert _tokens(eng) == cold
        # ...and serving itself added no lazy compiles
        assert eng.metrics.prefill_compiles == 0
        assert eng.metrics.decode_compiles == 0

    def test_warm_restart_zero_reanalysis(
        self, model, warm_cache, monkeypatch
    ):
        """The L3 summaries (collective census + per-chip memory) ride
        the artifact metadata: a warm restart reads them back instead
        of re-extracting HLO / re-running the memory analysis — zero
        re-analysis, same discipline as zero fresh traces."""
        import paddle_tpu.analysis.compiled as ac

        root, _ = warm_cache

        def _boom(compiled):
            raise AssertionError(
                "program_summary re-extracted on a warm restart"
            )

        monkeypatch.setattr(ac, "program_summary", _boom)
        eng = Engine(model, _engine_config(root))
        assert eng.metrics.decode_compiles == 0
        # per-program predicted peaks came from the meta sidecar
        assert eng.metrics.program_bytes.get("decode", 0) > 0
        # ...and the L3 rules re-evaluate over the stored summaries
        report = eng.check_compiled_programs()
        assert not report.errors, report.render()
        assert eng.health()["predicted_peak_bytes_per_chip"] > 0

    def test_manifest_entries_carry_memory(self, warm_cache):
        root, _ = warm_cache
        mdir = os.path.join(root, "manifests")
        (mname,) = os.listdir(mdir)
        with open(os.path.join(mdir, mname)) as f:
            entries = json.load(f)["entries"]
        assert entries and all(
            e.get("memory", 0) > 0 for e in entries
        )

    def test_manifest_lists_program_set(self, warm_cache):
        root, _ = warm_cache
        mdir = os.path.join(root, "manifests")
        (mname,) = os.listdir(mdir)
        with open(os.path.join(mdir, mname)) as f:
            entries = json.load(f)["entries"]
        kinds = sorted(e["kind"] for e in entries)
        assert kinds == ["decode", "prefill"]
        store = ArtifactStore(root)
        for e in entries:
            assert store.contains(e["store_key"])
            # the key embeds the adapter's code identity: an edited
            # adapter/model must miss, not hit the pre-edit executable
            assert "code=LlamaServingAdapter|" in e["signature"]

    def test_aot_hits_are_not_retraces(self, warm_cache, model):
        before = jit_events.retraces_after_warmup()
        Engine(model, _engine_config(warm_cache[0]))
        assert jit_events.retraces_after_warmup() == before
        log = [
            e for e in jit_events.compile_log()
            if e["kind"] == "aot-hit"
        ]
        assert log and all(not e["retrace"] for e in log)


class TestFailurePaths:
    """Corrupt / truncated / stale artifacts and injected faults all
    degrade to a fresh compile — warned and counted, never raised."""

    def _rebuild_and_check(self, model, root, cold, capsys, msg):
        cc = compilecache.resolve(root)
        f0 = cc.metrics.fallbacks
        eng = Engine(model, _engine_config(root))
        assert cc.metrics.fallbacks > f0
        assert eng.metrics.decode_compiles == 1   # decode recompiled
        assert eng.metrics.prefill_compiles == 0  # prefill still warm
        assert msg in capsys.readouterr().err
        assert _tokens(eng) == cold
        return cc

    def test_bit_flip_corruption_falls_back(
        self, model, warm_cache, tmp_path, capsys
    ):
        root, cold = warm_cache

        def flip(objects, entry):
            p = os.path.join(objects, entry["store_key"], "exec.bin")
            raw = bytearray(open(p, "rb").read())
            raw[len(raw) // 2] ^= 0x01
            open(p, "wb").write(bytes(raw))

        dst = _damaged_copy(root, tmp_path, flip)
        cc = self._rebuild_and_check(
            model, dst, cold, capsys, "falling back to a fresh compile"
        )
        # the known-bad artifact was dropped and re-published: the NEXT
        # restart is fully warm again
        eng = Engine(model, _engine_config(dst))
        assert eng.metrics.decode_compiles == 0
        assert cc.metrics.store_errors == 0

    def test_truncated_artifact_falls_back(
        self, model, warm_cache, tmp_path, capsys
    ):
        root, cold = warm_cache

        def truncate(objects, entry):
            p = os.path.join(objects, entry["store_key"], "exec.bin")
            raw = open(p, "rb").read()
            open(p, "wb").write(raw[: len(raw) // 2])

        dst = _damaged_copy(root, tmp_path, truncate)
        self._rebuild_and_check(
            model, dst, cold, capsys, "checksum mismatch"
        )

    def test_stale_version_entry_falls_back(
        self, model, warm_cache, tmp_path, capsys
    ):
        root, cold = warm_cache

        def stale(objects, entry):
            p = os.path.join(objects, entry["store_key"], "meta.json")
            meta = json.load(open(p))
            meta["env"]["jax"] = "0.0.1"
            json.dump(meta, open(p, "w"))

        dst = _damaged_copy(root, tmp_path, stale)
        self._rebuild_and_check(
            model, dst, cold, capsys, "environment mismatch"
        )

    def test_injected_load_fault_falls_back(
        self, model, warm_cache, tmp_path, capsys
    ):
        root, cold = warm_cache
        dst = str(tmp_path / "cache")
        shutil.copytree(root, dst)
        cc = compilecache.resolve(dst)
        f0 = cc.metrics.fallbacks
        with faults.inject({"cc.load": FaultSpec(
            OSError("injected read error"), every=1, max_fires=1,
        )}) as inj:
            eng = Engine(model, _engine_config(dst))
        assert inj.fired["cc.load"] == 1
        assert cc.metrics.fallbacks == f0 + 1
        assert "injected read error" in capsys.readouterr().err
        # exactly one program recompiled, the rest loaded warm
        total = eng.metrics.decode_compiles + eng.metrics.prefill_compiles
        assert total == 1
        assert _tokens(eng) == cold

    def test_injected_write_fault_degrades_to_cold_cache(
        self, model, tmp_path, capsys
    ):
        """A failed publish (``cc.write``: ENOSPC, torn filesystem) is
        a warning + counter — the engine itself compiles and serves
        normally; the atomic-rename discipline leaves NO partial
        artifact behind for a later restart to trip on."""
        root = str(tmp_path / "cache")
        with faults.inject({"cc.write": FaultSpec(
            OSError(28, "No space left on device"), every=1,
        )}) as inj:
            eng = Engine(model, _engine_config(root))
        assert inj.fired["cc.write"] >= 2
        cc = compilecache.resolve(root)
        assert cc.metrics.store_errors >= 2
        assert "failed to persist" in capsys.readouterr().err
        assert eng.metrics.decode_compiles == 1
        assert ArtifactStore(root).keys() == []  # nothing half-written
        assert not [
            n for n in os.listdir(root) if n.startswith(".tmp-")
        ]


PFX_CFG = dict(
    max_batch_slots=2, max_model_len=32, page_size=4,
    prefill_buckets=[8, 32], enable_prefix_cache=True,
    prefill_chunk_tokens=8, speculate_tokens=2,
)


@pytest.fixture(scope="module")
def warm_pfx_cache(model, tmp_path_factory):
    """One cold build of the ENLARGED program set (prefix caching +
    chunked prefill: prefill + prefill_ext per bucket, decode, COW),
    shared by the warm-restart and warm-CLI tests."""
    root = tmp_path_factory.mktemp("ccpfx")
    eng = Engine(model, _engine_config(root, **PFX_CFG))
    cold = _tokens(eng)
    m = eng.metrics
    assert m.prefill_compiles >= 1
    assert m.prefill_ext_compiles >= 1
    assert m.decode_compiles == 1
    assert m.cow_compiles == 1
    assert m.verify_compiles == 1
    return str(root), cold


class TestPrefixCacheWarmRestart:
    """The enlarged program set (prefix cache + chunked prefill) joins
    the manifest and replays on a warm restart with zero fresh
    traces."""

    def test_manifest_covers_enlarged_program_set(self, warm_pfx_cache):
        root, _ = warm_pfx_cache
        mdir = os.path.join(root, "manifests")
        (mname,) = os.listdir(mdir)
        with open(os.path.join(mdir, mname)) as f:
            entries = json.load(f)["entries"]
        kinds = sorted(set(e["kind"] for e in entries))
        assert kinds == ["cow", "decode", "prefill", "prefill_ext",
                         "verify"]
        ext_buckets = sorted(
            e["bucket"] for e in entries if e["kind"] == "prefill_ext"
        )
        assert ext_buckets == [8, 32]
        store = ArtifactStore(root)
        for e in entries:
            assert store.contains(e["store_key"])

    def test_warm_restart_replays_enlarged_set_zero_traces(
        self, model, warm_pfx_cache
    ):
        root, cold = warm_pfx_cache
        hits0 = jit_events.aot_hits()
        eng = Engine(model, _engine_config(root, **PFX_CFG))
        m = eng.metrics
        probe = (m.prefill_compiles, m.prefill_ext_compiles,
                 m.decode_compiles, m.cow_compiles, m.verify_compiles)
        assert probe == (0, 0, 0, 0, 0)
        assert jit_events.aot_hits() >= hits0 + 7  # 2+2 pf, decode, cow, verify
        # serving through the warm programs: bit-identical, still zero
        # traces — cache hits, chunked prefill and COW all replay AOT
        assert _tokens(eng) == cold
        assert _tokens(eng) == cold   # second pass: prefix-cache hits
        assert eng.metrics.prefix_hit_tokens > 0
        assert eng.metrics.cow_copies >= 1
        probe = (m.prefill_compiles, m.prefill_ext_compiles,
                 m.decode_compiles, m.cow_compiles, m.verify_compiles)
        assert probe == (0, 0, 0, 0, 0)


class TestWarmCLI:
    """``python -m paddle_tpu.compilecache warm --manifest <path>``:
    pre-populate / verify a fleet's cache ahead of deploy."""

    def _manifest_path(self, root):
        mdir = os.path.join(root, "manifests")
        (mname,) = os.listdir(mdir)
        return os.path.join(mdir, mname)

    def test_warm_verifies_full_cache(self, warm_pfx_cache, capsys):
        from paddle_tpu.compilecache.__main__ import main

        root, _ = warm_pfx_cache
        assert main(["warm", "--manifest", self._manifest_path(root)]) == 0
        out = capsys.readouterr().out
        assert "7/7 programs present" in out

    def test_warm_reports_missing_without_builder(
        self, warm_pfx_cache, tmp_path, capsys
    ):
        from paddle_tpu.compilecache.__main__ import main

        root, _ = warm_pfx_cache
        dst = str(tmp_path / "cache")
        shutil.copytree(root, dst)
        mpath = self._manifest_path(dst)
        with open(mpath) as f:
            entries = json.load(f)["entries"]
        (decode,) = [e for e in entries if e["kind"] == "decode"]
        ArtifactStore(dst).remove(decode["store_key"])
        assert main(["warm", "--manifest", mpath]) == 3
        out = capsys.readouterr().out
        assert "MISSING" in out and "6/7 programs present" in out

    def test_warm_builder_compiles_missing_entries(
        self, warm_pfx_cache, tmp_path, monkeypatch, capsys
    ):
        """With --builder, a partially-populated cache is completed:
        the builder constructs the service's engine against the cache
        (warm for everything present), and only the missing program
        compiles fresh and is re-persisted."""
        import sys as _sys

        from paddle_tpu.compilecache.__main__ import main

        root, _ = warm_pfx_cache
        dst = str(tmp_path / "cache")
        shutil.copytree(root, dst)
        mpath = self._manifest_path(dst)
        with open(mpath) as f:
            entries = json.load(f)["entries"]
        (cow,) = [e for e in entries if e["kind"] == "cow"]
        ArtifactStore(dst).remove(cow["store_key"])
        # the builder module a deploy pipeline would ship: rebuilds the
        # service's engine (same model identity + config -> same
        # service key) against the cache directory it is handed
        (tmp_path / "pfx_warm_builder.py").write_text(
            "import paddle_tpu as paddle\n"
            "from paddle_tpu.models.llama import LlamaConfig, "
            "LlamaForCausalLM\n"
            "from paddle_tpu.serving import Engine, EngineConfig\n"
            f"CFG = {PFX_CFG!r}\n"
            "def build(cache_dir):\n"
            "    paddle.seed(0)\n"
            "    model = LlamaForCausalLM(LlamaConfig.tiny())\n"
            "    Engine(model, EngineConfig(compile_cache=cache_dir, "
            "**CFG))\n"
        )
        monkeypatch.syspath_prepend(str(tmp_path))
        _sys.modules.pop("pfx_warm_builder", None)
        rc = main([
            "warm", "--manifest", mpath,
            "--builder", "pfx_warm_builder:build",
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "1/7 program(s) missing" in out
        assert "7/7 programs present" in out
        assert ArtifactStore(dst).contains(cow["store_key"])


class TestFleetWarmRestart:
    def test_rolling_restart_replays_manifest(self, model, warm_cache):
        root, cold = warm_cache
        fleet = Fleet(
            model, _engine_config(root),
            FleetConfig(num_replicas=2, max_restarts=1),
        )
        # every replica of a shared-cache fleet builds warm
        for sup in fleet.replicas:
            assert sup.engine.metrics.decode_compiles == 0
            assert sup.engine.metrics.prefill_compiles == 0
        fleet.rolling_restart(min_available=1)
        for sup in fleet.replicas:
            assert sup.status == "healthy"
            assert sup.engine.metrics.decode_compiles == 0
            assert sup.engine.metrics.prefill_compiles == 0
        outs = fleet.generate(
            PROMPTS, SamplingParams(max_new_tokens=6)
        )
        assert [tuple(o.token_ids) for o in outs] == cold


class TestToStaticCache:
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)

        def forward(self, x):
            return self.fc(x)

    def _build(self, cache):
        paddle.seed(7)
        return jit.to_static(self.Net(), cache=cache)

    def test_second_instance_loads_aot(self, tmp_path):
        cache = str(tmp_path / "ts")
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8).astype("float32")
        )
        cc = compilecache.resolve(cache)
        with paddle.no_grad():
            y1 = self._build(cache)(x)
            assert cc.metrics.misses == 1
            hits0 = jit_events.aot_hits()
            y2 = self._build(cache)(x)
        assert cc.metrics.hits == 1
        assert jit_events.aot_hits() == hits0 + 1
        assert (y1.numpy() == y2.numpy()).all()

    def test_cache_requires_full_graph(self):
        with pytest.raises(ValueError, match="full_graph"):
            jit.to_static(self.Net(), cache="/tmp/x", full_graph=False)

    def test_train_mode_is_part_of_the_key(self, tmp_path):
        """The layer's train/eval flag shapes the traced program
        (dropout) but not the abstract signature — flipping it must
        compile/load a DIFFERENT program, in-process and on disk, never
        replay the other mode's executable."""
        class DropNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 32)
                self.drop = nn.Dropout(0.5)

            def forward(self, x):
                return self.drop(self.fc(x))

        cache = str(tmp_path / "ts")
        cc = compilecache.resolve(cache)
        paddle.seed(11)
        net = DropNet()
        staged = jit.to_static(net, cache=cache)
        x = paddle.to_tensor(
            np.random.RandomState(1).randn(2, 8).astype("float32")
        )
        with paddle.no_grad():
            net.eval()
            y_eval = staged(x).numpy()
            net.train()
            y_train = staged(x).numpy()
        assert cc.metrics.misses == 2  # two distinct disk keys
        # train mode actually dropped units; eval mode did not
        assert (y_train == 0).any() and not (y_eval == 0).any()
        assert (y_train != y_eval).any()
        # a fresh instance in train mode must not hit the eval artifact
        paddle.seed(11)
        net2 = DropNet()
        net2.train()
        h0 = cc.metrics.hits
        with paddle.no_grad():
            y2 = jit.to_static(net2, cache=cache)(x).numpy()
        assert cc.metrics.hits == h0 + 1
        assert (y2 == 0).any()

    def test_unstable_static_arg_bypasses_disk(self, tmp_path, capsys):
        """A static arg with an address-bearing default repr cannot
        form a stable cross-process key: the signature compiles
        in-memory only (warned once), instead of storing one orphan
        artifact per process run."""
        class Knob:
            pass  # default object repr: "<...Knob object at 0x...>"

        def f(x, knob):
            return x * 2.0

        cache = str(tmp_path / "ts")
        cc = compilecache.resolve(cache)
        x = paddle.to_tensor(np.ones((2, 2), dtype="float32"))
        with paddle.no_grad():
            y = jit.to_static(f, cache=cache)(x, Knob())
        assert (y.numpy() == 2.0).all()
        assert "no stable repr" in capsys.readouterr().err
        snap = cc.metrics.snapshot()
        assert snap["hits"] == snap["misses"] == 0
        assert ArtifactStore(cache).keys() == []


class TestBucketedExport:
    """jit.save(bucket_sizes=) / load: one program per bucket, picked
    by shape with pad-up + slice-back."""

    @pytest.fixture(scope="class")
    def saved(self, tmp_path_factory):
        from paddle_tpu.jit import serialization as S

        d = tmp_path_factory.mktemp("export")
        paddle.seed(3)
        net = TestToStaticCache.Net()
        net.eval()
        S.save(
            net, str(d / "m"),
            input_spec=[S.InputSpec([None, 8], "float32")],
            bucket_sizes={0: [2, 4]},
        )
        return str(d / "m"), net

    def test_programs_per_bucket_on_disk(self, saved):
        path, _ = saved
        assert os.path.exists(path + ".b2.pdmodel")
        assert os.path.exists(path + ".b4.pdmodel")
        meta = json.load(open(path + ".pdmeta"))
        assert meta["buckets"] == {"dims": [0], "combos": [[2], [4]]}
        assert meta["jax_version"]

    def test_load_picks_pads_slices(self, saved):
        from paddle_tpu.jit import serialization as S

        path, net = saved
        tl = S.load(path)
        for n in (1, 2, 3, 4):
            x = paddle.to_tensor(
                np.random.RandomState(n).randn(n, 8).astype("float32")
            )
            ref = net(x).numpy()
            got = tl(x).numpy()
            assert got.shape == ref.shape
            np.testing.assert_allclose(got, ref, atol=1e-6)

    def test_fixed_output_dim_at_bucket_size_not_sliced(self, tmp_path):
        """Slice-back is derived from cross-combo out_avals, not
        guessed from sizes: an output whose axis is a FIXED size that
        happens to equal the padded bucket target must come back whole,
        while the batch-tracking output is sliced to the true size."""
        from paddle_tpu.jit import serialization as S

        class TableNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 3)

            def forward(self, x):
                # second output: fixed (4, 3) — axis 0 equals the
                # larger bucket size below but does NOT track batch
                return self.fc(x), paddle.ones([4, 3])

        paddle.seed(9)
        net = TableNet()
        net.eval()
        S.save(
            net, str(tmp_path / "m"),
            input_spec=[S.InputSpec([None, 8], "float32")],
            bucket_sizes={0: [2, 4]},
        )
        tl = S.load(str(tmp_path / "m"))
        x = paddle.to_tensor(
            np.random.RandomState(2).randn(3, 8).astype("float32")
        )
        pred, table = tl(x)  # n=3 -> bucket 4, slice-back to 3
        assert pred.shape == [3, 3]
        assert table.shape == [4, 3]  # NOT truncated to (3, 3)
        np.testing.assert_allclose(
            pred.numpy(), net(x)[0].numpy(), atol=1e-6
        )

    def test_oversize_input_errors_clearly(self, saved):
        from paddle_tpu.jit import serialization as S

        tl = S.load(saved[0])
        x = paddle.to_tensor(np.zeros((5, 8), dtype="float32"))
        with pytest.raises(ValueError, match="exceeds the largest"):
            tl(x)

    def test_missing_bucket_dim_rejected(self, tmp_path):
        from paddle_tpu.jit import serialization as S

        net = TestToStaticCache.Net()
        with pytest.raises(ValueError, match="dynamic dims"):
            S.save(
                net, str(tmp_path / "m"),
                input_spec=[S.InputSpec([None, 8], "float32")],
                bucket_sizes={1: [8]},
            )

    def test_version_mismatch_errors_clearly(self, saved, tmp_path):
        from paddle_tpu.jit import serialization as S

        src, _ = saved
        d = str(tmp_path / "m")
        for suffix in (".pdmeta", ".pdiparams", ".b2.pdmodel",
                       ".b4.pdmodel"):
            shutil.copy(src + suffix, d + suffix)
        meta = json.load(open(d + ".pdmeta"))
        meta["jax_version"] = "0.0.1"
        json.dump(meta, open(d + ".pdmeta", "w"))
        with open(d + ".b2.pdmodel", "r+b") as f:
            f.seek(16)
            f.write(b"\xff" * 8)
        with pytest.raises(ValueError, match="exported with jax 0.0.1"):
            S.load(d)


class TestCollectorView:
    def test_compilecache_series_exported(self, tmp_path):
        from paddle_tpu.observability import get_registry

        cc = CompileCache(str(tmp_path))
        cc.metrics.hits = 3
        cc.metrics.fallbacks = 1
        snap = get_registry().snapshot()
        label = "{cache=" + cc.root + "}"
        assert snap["paddle_tpu_compilecache_hits_total" + label] == 3
        assert (
            snap["paddle_tpu_compilecache_fallbacks_total" + label] == 1
        )

    def test_dump_marks_aot_hits_and_summarizes_cache(self):
        """``observability dump`` renders cache loads under their own
        ``aot-hit`` mark (not ``compile``/``RETRACE``) and aggregates
        the ``paddle_tpu_compilecache_*`` series into a hits/misses
        summary block."""
        import io

        from paddle_tpu.observability.__main__ import _render_dump

        payload = {
            "reason": "test", "pid": 1, "ts": 0.0,
            "compile_log": [
                {"ts": 0.0, "kind": "decode", "fn": "step",
                 "signature": "s", "retrace": False},
                {"ts": 0.0, "kind": "aot-hit", "fn": "step",
                 "signature": "s", "retrace": False,
                 "elapsed_s": 0.01},
            ],
            "metrics": {
                "paddle_tpu_compilecache_hits_total{cache=/a}": 2.0,
                "paddle_tpu_compilecache_hits_total{cache=/b}": 1.0,
                "paddle_tpu_compilecache_misses_total{cache=/a}": 4.0,
                "paddle_tpu_compilecache_fallbacks_total{cache=/a}": 1.0,
            },
        }
        out = io.StringIO()
        _render_dump(payload, out)
        text = out.getvalue()
        assert "compile  decode:step" in text
        assert "aot-hit  aot-hit:step" in text
        assert "hits=3 misses=4 fallbacks=1" in text
        assert "(aot-hit loads in log: 1)" in text
