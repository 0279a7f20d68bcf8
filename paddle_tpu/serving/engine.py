"""Continuous-batching LLM serving engine.

The Orca (OSDI '22) iteration-level scheduler on TPU-native constraints:
every XLA program must have a FIXED shape, so the batch is a static array
of ``max_batch_slots`` slots and occupancy is data, not shape — requests
join and leave mid-flight by mutating the slot arrays (tokens, positions,
block tables, active mask) while the compiled step is reused unchanged.
Two program families cover the whole serving loop after warmup (each in
a greedy-only and, when a sampled request is present, a with-sampler
variant — the mode is a static compile key, so an all-greedy fleet never
pays the vocab-wide sampling warp):

  * PREFILL: one prompt, padded to a length bucket
    (``jit.bucketing.next_bucket`` policy — at most len(buckets)
    compiles), writes the prompt's K/V into its pages and samples the
    first token.
  * DECODE: one token for every slot at once over the paged KV pool
    (``kv_cache.KVPool`` + per-request block tables), batched per-slot
    sampling (``sampler.sample_tokens``), one compile total.

Two more program families join the set when prefix caching or chunked
prefill is enabled (both bit-transparent to greedy outputs):

  * PREFILL_EXT: the bucketed prefill signature extended with a
    cache-length operand — continues a prompt whose first ``cache_len``
    tokens are already in the pages (an earlier chunk, or a shared
    prefix forked from the ``prefix_cache``), attending chunk tokens
    over the gathered page timeline in the exact ``_sdpa`` form the
    one-shot prefill uses (byte-identical logits and pages).
  * COW: copy one physical block (all layers) — the copy-on-write
    divergence step when a cache match's one-token-to-prefill cap cuts
    into the last shared block. One compile total.

And one more with speculative decoding (``speculate_tokens=K``):

  * VERIFY: score every greedy slot's K+1-token draft window (pending
    token + prompt-lookup drafts) in one launch and return per-position
    argmax targets; the engine accepts the longest target-matching
    draft prefix and emits accepted+1 tokens — byte-identical to plain
    greedy decode in up to (K+1)x fewer launches. One compile total;
    sampled slots keep the plain decode path.

Scheduling policy (host-side, cheap):
  * admission control — FCFS from the waiting queue into free slots,
    gated on KV blocks for the whole prompt plus one decode step;
    ``max_waiting`` bounds the queue. With the prefix cache enabled,
    the longest cached prompt prefix is matched at admission and its
    blocks are ``fork()``ed instead of allocated+recomputed; blocks
    whose only owner is the cache are reclaimed on demand before an
    admission is refused.
  * chunked prefill — ``prefill_chunk_tokens`` splits the remaining
    prompt into fixed-size chunks (padded through the same bucket set)
    and at most ``max_prefill_chunks_per_step`` chunks run per step,
    interleaved with the decode batch — one long prompt no longer
    stalls every running request for its whole prefill (Sarathi-style
    stall-free scheduling), bounding both TTFT and inter-token latency
    under mixed traffic.
  * block growth — each decode step first ensures every running request
    owns a block for the token it is about to write; on pool exhaustion
    the YOUNGEST running request is preempted (blocks freed, request
    requeued at the head). Preemption is recompute-style: the victim's
    tokens are kept and its cache is rebuilt by a later prefill over
    ``prompt + output[:-1]``, which restores its state exactly — greedy
    outputs are unchanged by preemption.

Engine counters live in ``metrics.EngineMetrics``; the compile counters
are incremented inside the traced step bodies, so they move only when XLA
actually retraces — the probe behind the no-recompile-after-warmup
guarantee.

Tensor parallelism (``EngineConfig(tp_degree=N, devices=)``,
serving/sharding.py): the same engine over N chips — weights sharded
col/row-wise and the KV pool's head dim split over a 1 x N mesh, every
program above still ONE single-launch SPMD program (GSPMD places the
collectives; the scheduler and every probe are chip-count-blind), with
``tp_numerics="exact"`` keeping outputs byte-identical to the
unsharded engine. ``tp_degree=1`` (default) is byte-identical to the
engine as it always was: no mesh, no placement, same jaxprs.
"""
from __future__ import annotations

import collections
import itertools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..core.device import on_tpu
from ..distributed.watchdog import CommTimeoutError, get_comm_watchdog
from ..jit.bucketing import next_bucket
from ..observability import flight as _flight
from ..observability import jit_events
from ..observability import register_health_provider, span
from ..observability import unregister_health_provider
from ..resilience import faults
from .access_log import record_finish
from .adapter import build_adapter
from .kv_cache import BlockManager, KVPool
from .metrics import EngineMetrics
from . import speculation
from .request import (
    Request,
    RequestOutput,
    RequestState,
    SamplingParams,
    normalize_sampling_params,
)
from .sampler import pack_sampling_params, sample_tokens

__all__ = ["Engine", "EngineConfig", "EngineOverloadedError"]


class EngineOverloadedError(RuntimeError):
    """add_request rejected under KV pressure (load shedding): the
    caller should back off / route elsewhere rather than deepen an
    already-saturated queue."""


# monotonic engine ids: id(self) gets reused by the allocator after an
# engine is collected, which would alias a fresh engine's probes,
# metric labels, and compile-log signatures onto a dead one's (a new
# engine's first compile must never read as a retrace alarm)
_engine_counter = itertools.count(1)


def _unregister_engine_probes(name):
    """weakref.finalize target: drop a collected engine's health
    provider and watchdog probe (module-level so the finalizer holds no
    reference back into the engine)."""
    unregister_health_provider(name)
    wd = get_comm_watchdog()
    if wd is not None and hasattr(wd, "unregister_probe"):
        wd.unregister_probe(name)


def _default_buckets(max_model_len):
    """Doubling ladder from 16 (or smaller) up to max_model_len."""
    buckets = []
    b = min(16, max_model_len)
    while b < max_model_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_model_len)
    return buckets


class EngineConfig:
    def __init__(self, max_batch_slots=8, max_model_len=2048, page_size=16,
                 num_blocks=None, prefill_buckets=None, max_waiting=None,
                 seed=0, kv_shed_threshold=None, analysis_check=None,
                 compile_cache=None, enable_prefix_cache=False,
                 prefix_cache_blocks=None, prefill_chunk_tokens=None,
                 max_prefill_chunks_per_step=1, speculate_tokens=None,
                 speculate_ngram=3, decode_kernel="auto",
                 kv_cache_dtype=None, journal=None, access_log=None,
                 slo=None, tp_degree=1, devices=None,
                 tp_numerics="exact", device_memory_budget=None,
                 stepstats=True, stepstats_ring=256,
                 host_spill_bytes=None, spill_dir=None):
        if max_batch_slots < 1:
            raise ValueError("max_batch_slots must be >= 1")
        if page_size < 1 or max_model_len < 2:
            raise ValueError("need page_size >= 1 and max_model_len >= 2")
        self.max_batch_slots = int(max_batch_slots)
        self.max_model_len = int(max_model_len)
        self.page_size = int(page_size)
        self.pages_per_seq = -(-self.max_model_len // self.page_size)
        self.num_blocks = int(
            num_blocks if num_blocks is not None
            else self.max_batch_slots * self.pages_per_seq
        )
        if self.num_blocks < self.pages_per_seq:
            raise ValueError(
                f"num_blocks ({self.num_blocks}) cannot hold even one "
                f"max-length request ({self.pages_per_seq} pages)"
            )
        self.prefill_buckets = sorted(
            int(b) for b in (prefill_buckets
                             or _default_buckets(self.max_model_len))
        )
        if self.prefill_buckets[-1] < self.max_model_len:
            raise ValueError(
                "largest prefill bucket must cover max_model_len "
                f"({self.prefill_buckets[-1]} < {self.max_model_len})"
            )
        if max_waiting is not None and max_waiting < 1:
            raise ValueError(
                f"max_waiting must be >= 1 or None (unbounded), got "
                f"{max_waiting}"
            )
        self.max_waiting = max_waiting
        if kv_shed_threshold is not None and not 0.0 < kv_shed_threshold <= 1.0:
            raise ValueError(
                f"kv_shed_threshold must be in (0, 1] or None, got "
                f"{kv_shed_threshold}"
            )
        # load shedding: when KV-pool utilization is at/above this
        # fraction AND the request cannot be admitted immediately,
        # add_request raises EngineOverloadedError instead of queueing
        self.kv_shed_threshold = kv_shed_threshold
        if analysis_check not in (None, "warn", "error"):
            raise ValueError(
                'analysis_check must be None, "warn" or "error", got '
                f"{analysis_check!r}"
            )
        # warmup gate: statically analyze the decode step at engine
        # build (paddle_tpu.analysis) and warn/raise on host-sync or
        # retrace findings — the static strengthening of the
        # compile-count probe
        self.analysis_check = analysis_check
        # persistent compile cache (paddle_tpu.compilecache): a path or
        # CompileCache. When set, the engine compiles its FULL program
        # set eagerly at build (every prefill bucket + the decode step),
        # serializes each executable to the cache, and records a warmup
        # manifest — so a restarting engine replays everything from disk
        # BEFORE accepting traffic, with zero fresh traces. None (the
        # default) keeps the lazy-compile behavior.
        self.compile_cache = compile_cache
        # automatic prefix caching (serving/prefix_cache.py): share
        # read-only prompt blocks across requests, retain them after
        # release under an LRU budget of prefix_cache_blocks entries
        # (None -> the whole pool is eligible)
        self.enable_prefix_cache = bool(enable_prefix_cache)
        if prefix_cache_blocks is not None and prefix_cache_blocks < 1:
            raise ValueError(
                f"prefix_cache_blocks must be >= 1 or None, got "
                f"{prefix_cache_blocks}"
            )
        self.prefix_cache_blocks = (
            int(prefix_cache_blocks) if prefix_cache_blocks is not None
            else self.num_blocks
        )
        # chunked prefill: None disables (a prompt prefills in one
        # launch, today's behavior); an int splits the remaining prompt
        # into chunks of that many tokens, each padded through the
        # prefill bucket set — pick a bucket size to avoid pad waste
        if prefill_chunk_tokens is not None:
            if prefill_chunk_tokens < 1:
                raise ValueError(
                    f"prefill_chunk_tokens must be >= 1 or None, got "
                    f"{prefill_chunk_tokens}"
                )
            if prefill_chunk_tokens > self.prefill_buckets[-1]:
                raise ValueError(
                    f"prefill_chunk_tokens ({prefill_chunk_tokens}) "
                    f"exceeds the largest prefill bucket "
                    f"({self.prefill_buckets[-1]})"
                )
        self.prefill_chunk_tokens = (
            None if prefill_chunk_tokens is None
            else int(prefill_chunk_tokens)
        )
        if max_prefill_chunks_per_step < 1:
            raise ValueError(
                f"max_prefill_chunks_per_step must be >= 1, got "
                f"{max_prefill_chunks_per_step}"
            )
        self.max_prefill_chunks_per_step = int(max_prefill_chunks_per_step)
        # speculative decoding: None disables (one decode launch = one
        # token, today's behavior); an int K routes greedy slots
        # through the VERIFY program — up to K prompt-lookup draft
        # tokens scored alongside the pending token in one launch, the
        # longest target-matching prefix accepted. Greedy outputs are
        # byte-identical either way; sampled slots keep the plain
        # decode path (and its key-stream discipline).
        if speculate_tokens is not None:
            if speculate_tokens < 1:
                raise ValueError(
                    f"speculate_tokens must be >= 1 or None (disabled), "
                    f"got {speculate_tokens}"
                )
            if speculate_tokens >= self.max_model_len:
                raise ValueError(
                    f"speculate_tokens ({speculate_tokens}) must be "
                    f"smaller than max_model_len ({self.max_model_len})"
                )
        self.speculate_tokens = (
            None if speculate_tokens is None else int(speculate_tokens)
        )
        if speculate_ngram < 1:
            raise ValueError(
                f"speculate_ngram must be >= 1, got {speculate_ngram}"
            )
        # longest trailing n-gram the prompt-lookup drafter matches on
        self.speculate_ngram = int(speculate_ngram)
        # decode attention path (kernels/pallas/paged_attention):
        # "auto" is Pallas on TPU under FLAGS_use_pallas_kernels, XLA
        # elsewhere; "pallas" requests the kernel — off-TPU (or under
        # tp sharding) that degrades to the XLA fallback with a warning
        # and a paddle_tpu_kernels_fallbacks_total count; on a TPU a
        # kernel Mosaic refuses is a compile error; "xla" pins the
        # fallback (the byte-reference path)
        if decode_kernel not in ("auto", "pallas", "xla"):
            raise ValueError(
                f'decode_kernel must be "auto", "pallas" or "xla", got '
                f"{decode_kernel!r}"
            )
        self.decode_kernel = decode_kernel
        # KV-cache quantization: None stores the adapter dtype (byte-
        # exact contracts hold); "int8" stores quantize-on-write int8
        # pages + per-token scales — ~4x smaller than an fp32 pool,
        # within the documented tolerance (docs/kernels.md), byte-exact
        # greedy contracts become tolerance contracts
        if kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f'kv_cache_dtype must be None or "int8", got '
                f"{kv_cache_dtype!r}"
            )
        self.kv_cache_dtype = kv_cache_dtype
        # durable request journal (serving/journal.py): a directory
        # path or a Journal. When set, every admission/token/finish is
        # WAL-logged and a restarting engine replays the journal
        # BEFORE traffic — unfinished requests re-admitted at the
        # queue head through the resume() re-prefill contract (greedy
        # byte-identical). None (the default) keeps serving state
        # process-local. For a Fleet use FleetConfig(journal_dir=)
        # instead: replicas share one fleet-level journal.
        self.journal = journal
        # structured JSONL access log (serving/access_log.py): a
        # directory path or AccessLog. One line per finished request
        # (rid, trace id, phase breakdown, finish reason), rotating
        # files, every failure degrading via the obs.accesslog fault
        # site — never fatal. None disables.
        self.access_log = access_log
        # latency SLO (observability.latency.SLOConfig): when set, the
        # engine tracks windowed TTFT/TPOT error-budget burn; sustained
        # burn flips health()["flags"] — and /healthz — to degraded.
        if slo is not None:
            from ..observability.latency import SLOConfig

            if not isinstance(slo, SLOConfig):
                raise TypeError(
                    f"slo must be an observability.SLOConfig or None, "
                    f"got {type(slo).__name__}"
                )
        self.slo = slo
        # tensor-parallel sharded serving (serving/sharding.py):
        # tp_degree > 1 builds a 1 x tp mesh over ``devices`` (jax
        # Device objects or integer ids; None takes the first
        # tp_degree of jax.devices()), shards the adapter weights
        # col/row-wise and the KV pool's head dim over it, and runs
        # every serving program as ONE single-launch SPMD program.
        # tp_degree=1 (the default) is byte-identical to the
        # single-chip engine — no mesh, no placement, same jaxprs.
        if int(tp_degree) < 1:
            raise ValueError(
                f"tp_degree must be >= 1, got {tp_degree}"
            )
        self.tp_degree = int(tp_degree)
        # materialized ONCE: a generator argument must not be consumed
        # by validation and then read empty at engine build
        self.devices = list(devices) if devices is not None else None
        if self.devices is not None and self.tp_degree == 1:
            # refusing beats silently ignoring: an operator pinning
            # per-replica chips must not discover at capacity review
            # that every tp=1 replica stacked on the default device
            raise ValueError(
                "EngineConfig(devices=) requires tp_degree > 1: a "
                "single-chip engine runs on the process's default "
                "device (devices= only places the tensor-parallel "
                "mesh)"
            )
        if (self.devices is not None
                and len(self.devices) != self.tp_degree):
            raise ValueError(
                f"EngineConfig(devices=) has {len(self.devices)} "
                f"entries but tp_degree={self.tp_degree} needs "
                f"exactly {self.tp_degree}"
            )
        # cross-chip numerics for the two row-parallel contractions:
        # "exact" (default) gathers the sharded operand so reductions
        # run whole on every chip — greedy outputs byte-identical to
        # the unsharded engine; "fast" is the Megatron partial-sum +
        # all-reduce, ~1 ulp reduction-order drift (docs/serving.md)
        if tp_numerics not in ("exact", "fast"):
            raise ValueError(
                f'tp_numerics must be "exact" or "fast", got '
                f"{tp_numerics!r}"
            )
        self.tp_numerics = tp_numerics
        # per-chip memory budget gate (paddle_tpu.analysis level 3,
        # docs/analysis.md): when set, the engine AOT-lowers its whole
        # program family at build and compares each program's predicted
        # per-chip peak (``compiled.memory_analysis()``) against this
        # byte budget — refusing the config with an AnalysisError
        # (``analysis_check="warn"`` degrades to a warning) BEFORE the
        # KV pool or any step buffer is allocated on a device. None
        # disables the gate.
        if device_memory_budget is not None:
            device_memory_budget = int(device_memory_budget)
            if device_memory_budget < 1:
                raise ValueError(
                    f"device_memory_budget must be >= 1 byte or None, "
                    f"got {device_memory_budget}"
                )
        self.device_memory_budget = device_memory_budget
        # serving step observatory (observability/stepstats.py): every
        # step folds into per-program launch-wall digests, a goodput
        # ledger, and a bounded sample ring of the last
        # ``stepstats_ring`` non-idle steps — host-side bumps on the
        # hot path, rendered pull-time only. stepstats=False removes
        # the sampler entirely (the bench overhead floor).
        self.stepstats = bool(stepstats)
        stepstats_ring = int(stepstats_ring)
        if stepstats_ring < 1:
            raise ValueError(
                f"stepstats_ring must be >= 1, got {stepstats_ring}"
            )
        self.stepstats_ring = stepstats_ring
        # hierarchical KV spill tier (serving/spill.py): when
        # host_spill_bytes is set, prefix-cache eviction and
        # preemption/release demote KV blocks to a host-RAM LRU of
        # this many bytes (restored instead of recomputed); spill_dir
        # adds the compilecache-style disk third tier under it —
        # host-LRU victims demote to disk and survive the process.
        if host_spill_bytes is not None:
            host_spill_bytes = int(host_spill_bytes)
            if host_spill_bytes < 1:
                raise ValueError(
                    f"host_spill_bytes must be >= 1 byte or None, got "
                    f"{host_spill_bytes}"
                )
        self.host_spill_bytes = host_spill_bytes
        if spill_dir is not None and host_spill_bytes is None:
            raise ValueError(
                "EngineConfig(spill_dir=) is the DISK tier under the "
                "host spill tier: set host_spill_bytes= too"
            )
        self.spill_dir = str(spill_dir) if spill_dir is not None else None
        self.seed = int(seed)


class Engine:
    """Multi-tenant serving over a single model replica.

        engine = serving.Engine(model, serving.EngineConfig(...))
        engine.add_request([1, 2, 3], serving.SamplingParams(max_new_tokens=8))
        while engine.has_unfinished():
            for out in engine.step():
                print(out.request_id, out.token_ids)
    """

    def __init__(self, model, config=None):
        self.config = config or EngineConfig()
        self.adapter = build_adapter(model)
        self.engine_id = f"{next(_engine_counter):x}"
        # the metrics object doubles as a registry collector view
        # (paddle_tpu_serving_* series labeled engine=<id>)
        self.metrics = EngineMetrics(engine_id=self.engine_id)
        cfg = self.config
        # per-request observability: the JSONL access log (shared per
        # directory — fleet replicas append to one log) and the SLO
        # burn tracker the collector view + health() read
        self.access_log = None
        if cfg.access_log is not None:
            from .access_log import resolve_access_log

            self.access_log = resolve_access_log(cfg.access_log)
        self.slo = None
        if cfg.slo is not None:
            from ..observability.latency import SLOTracker

            self.slo = SLOTracker(cfg.slo)
            self.metrics.slo = self.slo
        # tensor-parallel sharding (serving/sharding.py): validated and
        # built BEFORE the pool exists so a bad degree raises one clear
        # ValueError/TypeError naming the flag and dimension instead of
        # a deep XLA mesh failure at first launch
        self.tp = None
        if cfg.tp_degree > 1:
            from .sharding import build_tp_spec

            self.tp = build_tp_spec(self.adapter, cfg)
        # pool dtype: the adapter may declare it; default to the embed
        # table's dtype for dict-shaped weights (the Llama adapter)
        dtype = getattr(self.adapter, "dtype", None)
        if dtype is None:
            dtype = self.adapter.weights["embed"].dtype
        self._pool_dtype = dtype
        # shape-only pool twin (zero device allocation): the program
        # family is traced, lowered, and memory-gated against THIS, so
        # a config whose predicted per-chip peak exceeds
        # EngineConfig(device_memory_budget=) is refused before the
        # real pool ever allocates a byte — the level-3 strengthening
        # of the pool's shard-direct allocation discipline
        self._pool_abstract = KVPool.abstract(
            self.adapter.num_layers, self.adapter.num_kv_heads,
            cfg.num_blocks, cfg.page_size, self.adapter.head_dim, dtype,
            quant_dtype=cfg.kv_cache_dtype,
            sharding=(
                self.tp.pool_sharding if self.tp is not None else None
            ),
        )
        # decode-kernel selection lives on the adapter (the traced
        # decode body reads it). ALWAYS assigned when the knob exists —
        # an adapter reused across engines must not leak a previous
        # engine's selection into this one's traced programs (whose
        # cache signatures and health claim THIS config). A non-default
        # request against an adapter without the knob fails HERE with
        # the config flag named, not at first trace.
        self._decode_kernel = cfg.decode_kernel
        if self.tp is not None and cfg.decode_kernel != "xla":
            # the Pallas paged kernel has no SPMD partitioning rule: a
            # sharded pool routes decode attention through the XLA
            # gather path. An EXPLICIT "pallas" request degrades —
            # warned once, counted, never fatal (the fallback computes
            # the same math); "auto" just resolves to the available
            # path, no warning.
            if cfg.decode_kernel == "pallas":
                from ..kernels.pallas._compat import record_fallback

                record_fallback(
                    "paged_attention", "sharding",
                    hint=(
                        "tensor-parallel serving "
                        f"(EngineConfig(tp_degree={cfg.tp_degree})) "
                        "shards the KV pool; the kernel cannot run "
                        "under SPMD yet"
                    ),
                )
            self._decode_kernel = "xla"
        if hasattr(self.adapter, "decode_kernel"):
            self.adapter.decode_kernel = self._decode_kernel
        elif cfg.decode_kernel != "auto":
            raise TypeError(
                f"{type(self.adapter).__name__} has no decode_kernel "
                f"attribute, but EngineConfig(decode_kernel="
                f"{cfg.decode_kernel!r}) needs an adapter that can "
                "select its decode attention path"
            )
        # TP spec mirrors the decode-kernel discipline: always
        # (re)assigned when the attribute exists so a reused adapter
        # cannot leak a previous engine's mesh into this one's traced
        # programs; a sharded engine over an adapter without the knob
        # fails HERE with the flag named.
        if hasattr(self.adapter, "tp_spec"):
            self.adapter.tp_spec = self.tp
        elif self.tp is not None:
            raise TypeError(
                f"{type(self.adapter).__name__} has no tp_spec "
                f"attribute, but EngineConfig(tp_degree="
                f"{cfg.tp_degree}) needs an adapter whose traced "
                "bodies honor a tensor-parallel sharding spec"
            )
        # the weight tree launches pass to the compiled programs. A
        # sharded engine holds its OWN placed copy instead of mutating
        # adapter.weights — a shared adapter must not leak one engine's
        # mesh placement into another engine's launches (the same
        # anti-leak discipline as decode_kernel/tp_spec, but weights
        # cannot be "re-assigned back"). tp_degree=1 keeps reading the
        # adapter's tree dynamically, so ``refresh()`` after a weight
        # swap still propagates; a SHARDED engine binds at build —
        # rebuild it (or ``Fleet.rolling_restart(model=)``) to swap.
        self._tp_weights = None
        if self.tp is not None:
            # placement: weights per the col/row plan (the pool was
            # already allocated sharded above) — health() exports the
            # measured per-chip byte figure either way
            self._tp_weights = self.tp.shard_weights(
                self.adapter.weights
            )
        # exported as the paddle_tpu_serving_tp_degree gauge
        self.metrics.tp_degree = cfg.tp_degree
        self.waiting: collections.deque = collections.deque()
        self.slots: list = [None] * cfg.max_batch_slots
        # outputs for requests aborted between steps: emitted by the
        # NEXT step() so drivers blocked on completion (generate(), a
        # fleet drain) observe the abort instead of waiting forever
        self._aborted: list = []
        self._admit_counter = 0
        self._key_counter = 0
        self._base_key = jax.random.PRNGKey(cfg.seed)
        # shed-retry backoff (generate()): when every pending prompt
        # is shed and nothing is in flight, the submit loop must wait
        # out the pressure instead of spinning on no-op step() calls
        from ..resilience.retry import RetryPolicy

        self._shed_backoff = RetryPolicy(
            max_attempts=None, deadline=float("inf"),
            base_delay=0.001, max_delay=0.05, jitter=0.1, seed=cfg.seed,
        )
        # programs FIRST, against the abstract pool twin (a compile
        # cache warms the whole family here too) — so the memory gate
        # below can refuse a predicted-OOM config while zero pool
        # buffers exist on any device
        self._build_steps()
        if cfg.device_memory_budget is not None:
            self._enforce_memory_budget()
        # under TP the pool allocates DIRECTLY on the mesh (pages
        # sharded on the kv-head dim when GQA allows): a pool sized to
        # N chips' combined KV budget must never transiently
        # materialize whole on one chip — that transient IS the
        # single-chip RESOURCE_EXHAUSTED ceiling this feature removes
        try:
            self.pool = KVPool(
                self.adapter.num_layers, self.adapter.num_kv_heads,
                cfg.num_blocks, cfg.page_size, self.adapter.head_dim,
                dtype,
                quant_dtype=cfg.kv_cache_dtype,
                sharding=(
                    self.tp.pool_sharding if self.tp is not None
                    else None
                ),
                shard_degree=(
                    self.tp.tp_degree
                    if self.tp is not None and self.tp.kv_sharded else 1
                ),
            )
        except Exception as e:
            from .spill import is_resource_exhausted

            if is_resource_exhausted(e):
                # OOM-graceful pool growth: a backend allocation
                # failure becomes an admission-style refusal an
                # operator (or a fleet supervisor) can act on — shrink
                # num_blocks, enable kv_cache_dtype="int8", raise
                # tp_degree — instead of an opaque backend crash
                raise EngineOverloadedError(
                    f"KV pool allocation exhausted device memory "
                    f"({cfg.num_blocks} blocks x {cfg.page_size} "
                    f"tokens): reduce num_blocks, quantize the cache "
                    f"(kv_cache_dtype='int8'), or shard it wider "
                    f"(tp_degree) — {type(e).__name__}: {e}"
                ) from e
            raise
        self.block_manager = BlockManager(cfg.num_blocks, cfg.page_size)
        # host-RAM spill tier under the pool (serving/spill.py): the
        # prefix cache demotes evicted chain blocks into it, and
        # preemption/release park whole-request handles there so
        # re-admission restores instead of recomputing
        self.spill = None
        self._spill_seq = 0
        self._spill_signature = None
        self._spill_warned = False
        if cfg.host_spill_bytes is not None:
            from .spill import HostSpillTier, register_spill_view

            self.spill = HostSpillTier(
                cfg.host_spill_bytes, spill_dir=cfg.spill_dir,
                engine_id=self.engine_id,
            )
            self._spill_signature = self.pool.block_signature()
            register_spill_view(self.spill, self.engine_id)
        self.prefix_cache = None
        if cfg.enable_prefix_cache:
            from .prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(
                self.block_manager,
                capacity_blocks=cfg.prefix_cache_blocks,
                metrics=self.metrics,
                spill=self.spill, pool=self.pool,
            )
        # step observatory (observability/stepstats.py): per-program
        # launch-wall digests, goodput ledger, bounded sample ring,
        # live MFU — registered as its own weakref collector view. A
        # sampler crash (the obs.stepstats fault site) warns once and
        # disables it; serving never perturbs (_disable_stepstats).
        self.stepstats = None
        self._stepstats_warned = False
        if cfg.stepstats:
            from ..observability.stepstats import (
                StepStats, register_stepstats_view,
            )

            from ..core.device import device_peaks

            self.stepstats = StepStats(
                adapter=self.adapter, tp_degree=cfg.tp_degree,
                shard_degree=self.pool.shard_degree,
                ring=cfg.stepstats_ring,
                peak_flops_per_chip=(
                    device_peaks().bf16_flops if on_tpu() else None
                ),
            )
            register_stepstats_view(self.stepstats, self.engine_id)
        # KV headroom gauge (free + reclaimable blocks): what the
        # fleet's headroom-aware router weighs; meaningful from build
        # (an engine that never stepped has the whole pool free)
        self.metrics.kv_headroom_blocks = self.block_manager.num_free
        if cfg.analysis_check is not None:
            # the consolidated gate (L1 jaxpr checks over every enabled
            # program family + the L3 compiled checks when summaries
            # are already in hand — a cache-warmed family, or any
            # engine under the memory gate; lazy engines keep their
            # L1-only build cost)
            self.check_programs(
                cfg.analysis_check,
                compiled=bool(self._aot)
                or cfg.device_memory_budget is not None,
            )
        # durable request journal: replayed AFTER the programs exist
        # (a compile cache has already warmed every prefill bucket by
        # now, so recovery re-prefills are zero-trace) and BEFORE any
        # traffic. Unfinished journaled requests join the queue head.
        self.journal = None
        self._journal_replaying = False
        if cfg.journal is not None:
            from .journal import resolve_journal

            self.journal = resolve_journal(cfg.journal, seed=cfg.seed)
            self._replay_journal()
        # observability: a comm watchdog trip dumps this engine's health
        # snapshot next to the thread stacks, and the scrape endpoint's
        # /healthz aggregates the same snapshot. Registered through a
        # weakref so neither consumer pins a dead engine (weights + KV
        # pool) in memory; weakref.finalize unregisters both when the
        # engine is collected, so dead probes don't accumulate across
        # engine lifetimes.
        import weakref

        def _probe(ref=weakref.ref(self)):
            eng = ref()
            return None if eng is None else eng.health()

        probe_name = f"serving.engine.{self.engine_id}"
        register_health_provider(probe_name, _probe)
        wd = get_comm_watchdog()
        if wd is not None and hasattr(wd, "register_probe"):
            wd.register_probe(probe_name, _probe, owner=self)
        weakref.finalize(
            self, _unregister_engine_probes, probe_name
        )

    # -- compiled steps ------------------------------------------------------
    def _build_steps(self):
        adapter, metrics = self.adapter, self.metrics
        # donation keeps the pool single-buffered on TPU; CPU PJRT ignores
        # donation (and warns), so skip it there
        donate = (1, 2) if on_tpu() else ()
        # poison isolation needs to know whether a failed launch may
        # have consumed the donated pool buffers (see _decode_subset)
        self._pool_donated = bool(donate)

        # ``any_sample`` is STATIC (python bool): an all-greedy batch —
        # the common serving case — compiles a program with no sampling
        # warp at all, instead of computing and discarding it. At most
        # two decode programs exist (greedy-only and mixed).

        def prefill_fn(w, kp, vp, ids, length, block_table,
                       temperature, top_k, top_p, do_sample, key,
                       any_sample):
            metrics.prefill_compiles += 1   # traced-body compile probe
            jit_events.mark_traced()        # global compile/retrace log
            logits, kp, vp = adapter.prefill(
                w, kp, vp, ids, length, block_table
            )
            u = (
                jax.random.uniform(
                    key, (1,) + logits.shape, jnp.float32, 1e-9, 1.0
                ) if any_sample else None
            )
            tok = sample_tokens(
                logits[None], temperature[None], top_k[None], top_p[None],
                do_sample[None], u,
            )
            return tok[0], kp, vp

        def decode_fn(w, kp, vp, tokens, positions, block_tables, active,
                      temperature, top_k, top_p, do_sample, key,
                      any_sample):
            metrics.decode_compiles += 1    # traced-body compile probe
            jit_events.mark_traced()        # global compile/retrace log
            logits, kp, vp = adapter.decode(
                w, kp, vp, tokens, positions, block_tables, active
            )
            u = (
                jax.random.uniform(
                    key, logits.shape, jnp.float32, 1e-9, 1.0
                ) if any_sample else None
            )
            nxt = sample_tokens(
                logits, temperature, top_k, top_p, do_sample, u
            )
            return nxt, kp, vp

        # chunked prefill / prefix-cache continuation: the bucketed
        # prefill signature with a cache-length operand. ``any_sample``
        # is forced False for non-final chunks host-side (their sampled
        # token is discarded), so only the final chunk of a sampled
        # request pays the warp.
        def prefill_ext_fn(w, kp, vp, ids, length, cache_len, block_table,
                           temperature, top_k, top_p, do_sample, key,
                           any_sample):
            metrics.prefill_ext_compiles += 1  # traced-body compile probe
            jit_events.mark_traced()           # global compile/retrace log
            logits, kp, vp = adapter.prefill_ext(
                w, kp, vp, ids, length, cache_len, block_table
            )
            u = (
                jax.random.uniform(
                    key, (1,) + logits.shape, jnp.float32, 1e-9, 1.0
                ) if any_sample else None
            )
            tok = sample_tokens(
                logits[None], temperature[None], top_k[None], top_p[None],
                do_sample[None], u,
            )
            return tok[0], kp, vp

        # copy-on-write divergence: duplicate one physical block across
        # every layer's pages (the partial shared block a cache match
        # would otherwise write into). tree_map: an int8 pool's scale
        # planes share the [*, blocks, ...] layout and copy the same way
        def cow_fn(kp, vp, src, dst):
            metrics.cow_compiles += 1       # traced-body compile probe
            jit_events.mark_traced()        # global compile/retrace log
            copy = lambda p: p.at[:, dst].set(p[:, src])
            kp = jax.tree_util.tree_map(copy, tuple(kp))
            vp = jax.tree_util.tree_map(copy, tuple(vp))
            return kp, vp

        # speculative verification: score every slot's K+1-token draft
        # window in one launch and return the per-position greedy
        # argmax — the targets the host-side accept loop compares the
        # drafts against. Greedy-only by design (sampled slots keep the
        # plain decode path), so there is no sampling variant and no
        # key operand: ONE program per engine, ever.
        def verify_fn(w, kp, vp, tokens, positions, draft_lens,
                      block_tables, active):
            metrics.verify_compiles += 1    # traced-body compile probe
            jit_events.mark_traced()        # global compile/retrace log
            logits, kp, vp = adapter.verify(
                w, kp, vp, tokens, positions, draft_lens, block_tables,
                active,
            )
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), kp, vp

        self._prefill_fn = prefill_fn   # unjitted: analysis traces these
        self._decode_fn = decode_fn
        self._prefill_ext_fn = prefill_ext_fn
        self._cow_fn = cow_fn
        self._verify_fn = verify_fn
        # tensor parallelism: pin the traced bodies' OUT shardings to
        # the pool's placement (tokens replicated). Outputs must
        # round-trip the input sharding exactly — a drifting output
        # placement would miss the compiled program's input layout on
        # the next launch and retrace, breaking the single-compile
        # probe. In shardings ride on the committed input arrays (lazy
        # path) / the sharding-attached abstract args (AOT path).
        if self.tp is not None:
            # out shardings come from the abstract pool twin (leaves
            # carry the same NamedSharding the real pool allocates
            # under), so the jits exist before any pool buffer does
            kp_sh, vp_sh = self.tp.pool_out_shardings(
                self._pool_abstract
            )
            rep = self.tp.replicated
            osh = {
                "prefill": (rep, kp_sh, vp_sh),
                "decode": (rep, kp_sh, vp_sh),
                "prefill_ext": (rep, kp_sh, vp_sh),
                "cow": (kp_sh, vp_sh),
                "verify": (rep, kp_sh, vp_sh),
            }
            jkw = lambda kind: {"out_shardings": osh[kind]}
        else:
            jkw = lambda kind: {}
        # the raw bodies and the exact jit options per program kind —
        # shared by the launch jits below, the L1 analysis checks, and
        # the isolated L3 lowering path (_lower_isolated), so all three
        # always describe the SAME program
        self._step_fns = {
            "prefill": prefill_fn,
            "decode": decode_fn,
            "prefill_ext": prefill_ext_fn,
            "cow": cow_fn,
            "verify": verify_fn,
        }
        self._jit_specs = {
            "prefill": dict(
                donate_argnums=donate, static_argnums=(11,),
                **jkw("prefill"),
            ),
            "decode": dict(
                donate_argnums=donate, static_argnums=(12,),
                **jkw("decode"),
            ),
            "prefill_ext": dict(
                donate_argnums=donate, static_argnums=(12,),
                **jkw("prefill_ext"),
            ),
            "cow": dict(
                donate_argnums=(0, 1) if self._pool_donated else (),
                **jkw("cow"),
            ),
            "verify": dict(donate_argnums=donate, **jkw("verify")),
        }
        self._prefill_jit = jax.jit(
            prefill_fn, **self._jit_specs["prefill"]
        )
        self._decode_jit = jax.jit(
            decode_fn, **self._jit_specs["decode"]
        )
        self._prefill_ext_jit = jax.jit(
            prefill_ext_fn, **self._jit_specs["prefill_ext"]
        )
        self._cow_jit = jax.jit(cow_fn, **self._jit_specs["cow"])
        self._verify_jit = jax.jit(
            verify_fn, **self._jit_specs["verify"]
        )
        cfg = self.config
        self._chunking = cfg.prefill_chunk_tokens is not None
        self._use_ext = self._chunking or cfg.enable_prefix_cache
        self._speculating = cfg.speculate_tokens is not None
        # optional-entry-point gates, at BUILD time: one clear error
        # naming the missing adapter method and the config flag that
        # needs it, instead of a deep trace-time AttributeError on the
        # first launch that would have used it
        if self._use_ext and not hasattr(adapter, "prefill_ext"):
            flags = [
                f for f, on in (
                    ("enable_prefix_cache=True", cfg.enable_prefix_cache),
                    (f"prefill_chunk_tokens={cfg.prefill_chunk_tokens}",
                     self._chunking),
                ) if on
            ]
            raise TypeError(
                f"{type(adapter).__name__} has no prefill_ext entry "
                f"point, but EngineConfig({', '.join(flags)}) needs an "
                "adapter that can continue a prefill at a nonzero "
                "cache length"
            )
        if self._speculating and not hasattr(adapter, "verify"):
            raise TypeError(
                f"{type(adapter).__name__} has no verify entry point, "
                f"but EngineConfig(speculate_tokens="
                f"{cfg.speculate_tokens}) needs an adapter that can "
                "score a K+1-token draft window in one launch"
            )
        # persistent compile cache: with a cache configured, every
        # launch goes through an AOT-compiled executable held in
        # self._aot — loaded from disk on a warm restart (zero fresh
        # traces; the traced-body probes above never fire) or compiled
        # once and serialized on a cold start
        self._cc = None
        self._aot = {}
        self._manifest = None
        self._warming = False
        # L3 compiled-analysis summaries (collective census + memory)
        # per program tag, however obtained: read back from a
        # compile-cache artifact's metadata (warm restart — zero
        # re-analysis), extracted once at store time (cold cache), or
        # an isolated AOT lowering (lazy engine under the memory gate /
        # an explicit check_programs() call)
        self._program_analysis: dict = {}
        from ..compilecache import code_fingerprint

        # the adapter's code identity: the engine's programs close over
        # adapter.prefill/decode, whose bytecode the abstract weight
        # tree cannot see — without this an edited model would hit the
        # pre-edit executable. Shallow like every bytecode fingerprint
        # (docs/compilecache.md): callees of these methods are not
        # covered (framework-internal callees are pinned by the env
        # fingerprint's framework version).
        self._adapter_code_fp = "|".join((
            type(self.adapter).__qualname__,
            code_fingerprint(getattr(self.adapter, "prefill", None))
            or "?",
            code_fingerprint(getattr(self.adapter, "decode", None))
            or "?",
            code_fingerprint(getattr(self.adapter, "prefill_ext", None))
            or "?",
            code_fingerprint(getattr(self.adapter, "verify", None))
            or "?",
        ))
        if self.config.compile_cache is not None:
            from .. import compilecache as _cc_mod

            self._cc = _cc_mod.resolve(self.config.compile_cache)
            self._warm_from_cache()

    # -- persistent compile cache (paddle_tpu.compilecache) ------------------
    def _abstract_args(self, kind, bucket=None):
        """ShapeDtypeStructs mirroring exactly what the launch sites
        pass, so an AOT-lowered program is byte-for-byte the program
        the lazy jit path would have compiled (bit-identical outputs by
        construction)."""
        from ..compilecache import abstractify

        cfg = self.config
        n = cfg.max_batch_slots
        sds = jax.ShapeDtypeStruct
        if self.tp is not None:
            # shardings attached: AOT lowering sees the exact operand
            # placements the lazy path's committed arrays carry, so the
            # cached executable IS the program a cold launch compiles
            w = self.tp.abstract(self._launch_weights())
        else:
            w = abstractify(self._launch_weights())
        # the abstract pool twin already carries the pool's exact
        # layout (and placement under TP) and exists before the real
        # pool does — the memory gate lowers from it pre-allocation
        kp = self._pool_abstract.k
        vp = self._pool_abstract.v
        key = sds(self._base_key.shape, self._base_key.dtype)
        if kind == "prefill":
            return (
                w, kp, vp,
                sds((int(bucket),), jnp.int32), sds((), jnp.int32),
                sds((cfg.pages_per_seq,), jnp.int32),
                sds((), jnp.float32), sds((), jnp.int32),
                sds((), jnp.float32), sds((), jnp.bool_), key,
            )
        if kind == "prefill_ext":
            return (
                w, kp, vp,
                sds((int(bucket),), jnp.int32), sds((), jnp.int32),
                sds((), jnp.int32),  # cache_len
                sds((cfg.pages_per_seq,), jnp.int32),
                sds((), jnp.float32), sds((), jnp.int32),
                sds((), jnp.float32), sds((), jnp.bool_), key,
            )
        if kind == "cow":
            return (kp, vp, sds((), jnp.int32), sds((), jnp.int32))
        if kind == "verify":
            return (
                w, kp, vp,
                sds((n, cfg.speculate_tokens + 1), jnp.int32),
                sds((n,), jnp.int32), sds((n,), jnp.int32),
                sds((n, cfg.pages_per_seq), jnp.int32),
                sds((n,), jnp.bool_),
            )
        return (
            w, kp, vp,
            sds((n,), jnp.int32), sds((n,), jnp.int32),
            sds((n, cfg.pages_per_seq), jnp.int32), sds((n,), jnp.bool_),
            sds((n,), jnp.float32), sds((n,), jnp.int32),
            sds((n,), jnp.float32), sds((n,), jnp.bool_), key,
        )

    def _program_meta(self, kind, bucket=None, any_sample=False):
        """``(name, signature, store_key)`` — one program's identity
        under the compile cache (``store_key`` is None without one).
        Factored out of :meth:`_ensure_program` so the L3 summary path
        can address an artifact's metadata sidecar without loading the
        executable."""
        from .. import compilecache as _cc_mod

        aargs = self._abstract_args(kind, bucket)
        name = f"serving.{kind}"
        # no explicit spec-K component: the verify window's K is
        # already pinned by the abstract tokens shape (n, K+1) inside
        # signature_str, and adding a constant to the other kinds'
        # signatures would invalidate every pre-existing on-disk
        # program for nothing
        # tp joins the signature only when sharding is on (keeps every
        # pre-existing single-chip on-disk program valid); dk is the
        # EFFECTIVE kernel (a sharded engine's "pallas" degraded to
        # "xla" must key the program actually built)
        # device ids join the sharded signature too: deserialized
        # executables are DEVICE-PINNED (one compiled for a mesh over
        # [0,1] fails its input-sharding check when launched on [4,5]),
        # so two fleet replicas on different placement slices must
        # never alias to one cached program. devices=None resolves to
        # the first tp ids, so pre-existing sharded caches stay warm.
        tp_sig = (
            f"tp={self.config.tp_degree}:"
            f"tpn={self.config.tp_numerics}:"
            f"dev={','.join(str(i) for i in self.tp.device_ids)}:"
            if self.tp is not None else ""
        )
        sig = (
            f"{kind}:bucket={bucket}:any_sample={bool(any_sample)}:"
            f"dk={self._decode_kernel}:{tp_sig}"
            f"code={self._adapter_code_fp}:"
            + _cc_mod.signature_str(aargs)
        )
        key = self._cc.key(name, sig) if self._cc is not None else None
        return name, sig, key

    def _record_summary(self, kind, bucket, any_sample, summary):
        """Memoize one program's L3 summary and export its predicted
        per-chip peak (``paddle_tpu_serving_program_bytes`` gauge via
        the metrics view, ``health()``'s predicted-peak field)."""
        if summary is None:
            return
        self._program_analysis[
            (kind, bucket, bool(any_sample))
        ] = summary
        mem = summary.get("memory")
        if mem:
            label = kind if bucket is None else f"{kind}[{bucket}]"
            if any_sample:
                label += "+sample"
            self.metrics.program_bytes[label] = int(mem["peak"])

    def _ensure_program(self, kind, bucket=None, any_sample=False):
        """Load-or-compile one serving program under the compile cache.
        A disk hit installs the deserialized executable (recorded as an
        ``aot-hit`` event — zero traces, the compile probes stay
        still) and reads the L3 analysis summary from the artifact's
        metadata sidecar (zero re-analysis); a miss lowers + compiles
        the SAME jitted function once (probes fire normally), extracts
        the summary, serializes both to the store, and appends the
        program to the warmup manifest so the next engine life replays
        everything from disk."""
        any_sample = bool(any_sample)
        tag = (kind, bucket, any_sample)
        exe = self._aot.get(tag)
        if exe is not None:
            return exe
        name, sig, key = self._program_meta(kind, bucket, any_sample)
        aargs = self._abstract_args(kind, bucket)
        summary = None
        got = self._cc.load_executable_bundle(
            key, name=name, signature=sig
        )
        if got is not None:
            exe, meta, _ = got
            summary = meta.get("analysis")
        else:
            exe = None
        if exe is None:
            jitted = {
                "prefill": self._prefill_jit,
                "prefill_ext": self._prefill_ext_jit,
                "decode": self._decode_jit,
                "cow": self._cow_jit,
                "verify": self._verify_jit,
            }[kind]
            if kind in ("prefill", "prefill_ext"):
                ev_sig = (f"{self.engine_id}:bucket={bucket}"
                          f":any_sample={any_sample}")
            elif kind == "decode":
                ev_sig = f"{self.engine_id}:any_sample={any_sample}"
            elif kind == "verify":
                ev_sig = (f"{self.engine_id}"
                          f":k={self.config.speculate_tokens}")
            else:
                ev_sig = self.engine_id
            with jit_events.watch(name, kind="serving", signature=ev_sig):
                if kind in ("cow", "verify"):
                    # no static sampling variant: cow copies blocks,
                    # verify is greedy-only by contract
                    exe = jitted.lower(*aargs).compile()
                else:
                    exe = jitted.lower(*aargs, any_sample).compile()
            try:
                from ..analysis.compiled import program_summary

                summary = program_summary(exe)
            except Exception:
                # analysis: allow(broad-except) the L3 summary is a
                # best-effort sidecar: a backend that cannot render it
                # must never block the compile it describes
                summary = None
            self._cc.store_executable(
                key, exe, name=name, signature=sig,
                extra_meta=(
                    {"analysis": summary} if summary is not None
                    else None
                ),
            )
        self._aot[tag] = exe
        self._record_summary(kind, bucket, any_sample, summary)
        if self._manifest is not None:
            extra = {}
            mem = (summary or {}).get("memory")
            if mem:
                # predicted per-chip peak rides the manifest entry, so
                # an operator can audit a service's byte budget from
                # the manifest alone (docs/compilecache.md)
                extra["memory"] = int(mem["peak"])
            self._manifest.add(
                name, sig, key, kind=kind, bucket=bucket,
                any_sample=any_sample, **extra,
            )
            # warmup batches one save after its replay loop; only a
            # program first traced MID-SERVING flushes immediately
            if not self._warming:
                self._save_manifest()
        return exe

    def _save_manifest(self):
        try:
            self._manifest.save()
        except OSError as e:
            import sys

            sys.stderr.write(
                f"[compilecache] manifest save failed (warm restart "
                f"will miss lazily-added programs): {e}\n"
            )

    def _warm_from_cache(self):
        """Replay the warmup manifest from disk before accepting
        traffic: the baseline program set (every prefill bucket plus
        the greedy decode step) is always warmed; any extra programs a
        previous engine life traced lazily (with-sampler variants) are
        replayed from its manifest. On a cache-warm restart this is
        pure deserialization — zero fresh traces."""
        cfg = self.config
        import hashlib

        from ..compilecache import abstractify, signature_str

        # the abstract pool twin stands in for pool.k: signature_str
        # covers treedef + shape/dtype only, so the service key string
        # is byte-identical to one computed from the real pool — every
        # pre-existing manifest stays live (the adapter code identity
        # is computed in _build_steps, before any cache work)
        svc = (
            signature_str((
                abstractify(self._launch_weights()),
                abstractify(self._pool_abstract.k),
            ))
            + f"|slots={cfg.max_batch_slots}|mml={cfg.max_model_len}"
            + f"|page={cfg.page_size}|blocks={cfg.num_blocks}"
            + f"|buckets={cfg.prefill_buckets}"
            + f"|chunk={cfg.prefill_chunk_tokens}"
            + f"|pfx={int(cfg.enable_prefix_cache)}"
            + f"|spec={cfg.speculate_tokens}"
            # dk is the EFFECTIVE kernel (matches the per-program
            # signatures): sharded engines configured "pallas" and
            # "xla" build byte-identical program sets and must share
            # one manifest; at tp=1 effective == configured, so every
            # pre-existing single-chip service key is unchanged
            + f"|dk={self._decode_kernel}|kvq={cfg.kv_cache_dtype}"
            # tp= keys the service only when sharding is on, so every
            # single-chip manifest written before this existed stays
            # live; a sharded engine warm-restarts from its OWN tp=N
            # manifest (docs/compilecache.md). dev= pins the manifest
            # to the placement slice — cached executables are
            # device-pinned, so each slice warms its own program set
            + (f"|tp={cfg.tp_degree}|tpn={cfg.tp_numerics}"
               f"|dev={','.join(str(i) for i in self.tp.device_ids)}"
               if self.tp is not None else "")
            + f"|code={self._adapter_code_fp}"
        )
        self._service_key = hashlib.sha256(svc.encode()).hexdigest()[:16]
        self._manifest = self._cc.manifest(self._service_key)
        replay = list(self._manifest.load())
        m = self._cc.metrics
        before = (m.hits, m.misses, m.fallbacks)
        self._warming = True
        try:
            self._ensure_program("decode", any_sample=False)
            for b in cfg.prefill_buckets:
                self._ensure_program(
                    "prefill", bucket=b, any_sample=False
                )
            if self._use_ext:
                # the enlarged program set: every bucket's continuation
                # program, plus the COW block copy when sharing is on
                for b in cfg.prefill_buckets:
                    self._ensure_program(
                        "prefill_ext", bucket=b, any_sample=False
                    )
                if cfg.enable_prefix_cache:
                    self._ensure_program("cow")
            if self._speculating:
                self._ensure_program("verify")
            for e in replay:
                kind, bucket = e.get("kind"), e.get("bucket")
                if kind == "prefill" and bucket in cfg.prefill_buckets:
                    self._ensure_program(
                        "prefill", bucket=bucket,
                        any_sample=e.get("any_sample", False),
                    )
                elif (kind == "prefill_ext" and self._use_ext
                        and bucket in cfg.prefill_buckets):
                    self._ensure_program(
                        "prefill_ext", bucket=bucket,
                        any_sample=e.get("any_sample", False),
                    )
                elif kind == "decode":
                    self._ensure_program(
                        "decode", any_sample=e.get("any_sample", False)
                    )
                elif kind == "cow" and cfg.enable_prefix_cache:
                    self._ensure_program("cow")
                elif kind == "verify" and self._speculating:
                    self._ensure_program("verify")
        finally:
            self._warming = False
        self._save_manifest()  # one fsync'd rewrite for the whole set
        _flight.record(
            "compilecache", "warm-start", engine=self.engine_id,
            hits=m.hits - before[0], misses=m.misses - before[1],
            fallbacks=m.fallbacks - before[2],
        )

    # -- durable request journal (serving/journal.py) ------------------------
    def _replay_journal(self):
        """Crash recovery: fold the journal into unfinished requests
        and re-admit them at the HEAD of the waiting queue (they have
        been waiting longest), oldest first. Each carries its emitted
        tokens, so the resume() re-prefill rebuilds its KV over
        ``prompt + output[:-1]`` — greedy continuation is
        byte-identical to an uninterrupted run and no journaled token
        is re-emitted. Requests whose TTL lapsed while the process was
        down are retired with ``"timeout"`` instead of re-prefilled
        (deadline-aware recovery). The re-admissions are re-journaled
        (ADMIT with cursor) so the dead incarnation's segments can
        compact as soon as the recovered work drains."""
        from .journal import restore_entries

        entries = self.journal.replay()
        if not entries:
            self.journal.flush()
            return
        live, expired = restore_entries(
            self.journal, entries,
            lambda e, params: Request(e.prompt, params,
                                      request_id=e.rid),
        )
        self.metrics.requests_timeout += expired
        self._journal_replaying = True
        try:
            for req in reversed(live):
                self.resume(req)
        finally:
            self._journal_replaying = False
        for req in live:   # re-ADMIT in admission order, cursor kept
            self.journal.admit(req)
        self.journal.flush()
        _flight.record(
            "serving", "journal-recovered", engine=self.engine_id,
            requests=len(live),
            expired=len(entries) - len(live),
        )

    # -- static analysis gates (paddle_tpu.analysis L1 + L3) -----------------
    def check_programs(self, mode="error", compiled=True):
        """THE analysis gate over this engine's whole program family.

        Level 1 (jaxpr): the decode step, the continuation prefill +
        COW copy (when enabled), and the speculative verify step (when
        enabled) are traced — never executed — and held to zero
        host-sync / retrace findings, exactly as the per-program
        ``check_decode``/``check_prefill``/``check_verify`` delegates
        always did. Level 3 (compiled, ``compiled=True``): every
        program in the family is AOT-lowered and its optimized HLO +
        memory analysis run through the collective census and the
        per-chip memory budget gate (``analysis.check_compiled``
        rules); findings are enforced per ``mode`` via
        ``analysis.enforce``.

        ``EngineConfig(analysis_check=)`` runs this at build (L3
        included when the family is already compiled — a cache-warmed
        engine — or the memory gate armed it; lazy engines keep their
        L1-only build cost). Returns the merged analysis Report.

        ``mode``: "error" raises ``analysis.AnalysisError`` on a
        blocking finding (and on an analyzer failure); "warn" degrades
        everything to warnings — analysis never takes down serving.
        """
        from .. import analysis

        if mode not in ("warn", "error"):
            raise ValueError(
                f'check_programs mode must be "warn" or "error", got '
                f"{mode!r}"
            )
        report = analysis.Report()
        report.extend(self._check_decode(mode).findings)
        if self._use_ext:
            report.extend(self._check_prefill(mode).findings)
        if self._speculating:
            report.extend(self._check_verify(mode).findings)
        if compiled:
            r3 = self.check_compiled_programs()
            analysis.enforce(
                r3, mode, what="serving compiled program family"
            )
            report.extend(r3.findings)
        return report

    def check_decode(self, mode="error"):
        """Thin delegate: the decode slice of :meth:`check_programs`
        (level 1 only), kept for callers that gate one program."""
        return self._check_decode(mode)

    def check_prefill(self, mode="error"):
        """Thin delegate: the continuation-prefill / COW slice of
        :meth:`check_programs` (level 1 only)."""
        return self._check_prefill(mode)

    def check_verify(self, mode="error"):
        """Thin delegate: the speculative-verify slice of
        :meth:`check_programs` (level 1 only)."""
        return self._check_verify(mode)

    def _program_tags(self):
        """Every ``(kind, bucket, any_sample)`` in this engine's
        baseline program family — the set ``_warm_from_cache`` warms
        and the L3 checks census."""
        cfg = self.config
        tags = [("decode", None, False)]
        tags += [("prefill", b, False) for b in cfg.prefill_buckets]
        if self._use_ext:
            tags += [
                ("prefill_ext", b, False) for b in cfg.prefill_buckets
            ]
            if cfg.enable_prefix_cache:
                tags.append(("cow", None, False))
        if self._speculating:
            tags.append(("verify", None, False))
        return tags

    def _lower_isolated(self, kind, bucket=None, any_sample=False):
        """AOT-compile one program for analysis WITHOUT touching the
        launch jits' trace caches or the compile telemetry: a fresh
        lambda owns its own pjit cache entry, so the real first launch
        still traces (and counts) exactly as before; the traced-body
        probes this trace fires are snapshot-restored and the
        compile/retrace event log is masked — the L3 counterpart of
        the L1 harness's isolation discipline."""
        fn = self._step_fns[kind]
        aargs = self._abstract_args(kind, bucket)
        m = self.metrics
        saved = (m.prefill_compiles, m.decode_compiles,
                 m.prefill_ext_compiles, m.cow_compiles,
                 m.verify_compiles)
        self._pin_adapter()
        try:
            with jit_events.suppress():
                fresh = jax.jit(
                    lambda *a: fn(*a), **self._jit_specs[kind]
                )
                if kind in ("cow", "verify"):
                    return fresh.lower(*aargs).compile()
                return fresh.lower(*aargs, bool(any_sample)).compile()
        finally:
            (m.prefill_compiles, m.decode_compiles,
             m.prefill_ext_compiles, m.cow_compiles,
             m.verify_compiles) = saved

    def _program_summary(self, kind, bucket=None, any_sample=False):
        """One program's L3 summary (collective census + per-chip
        memory), cheapest source first: the in-process memo, the
        compile-cache artifact's metadata sidecar (a warm restart
        re-evaluates rules with ZERO re-analysis), the executable
        ``_ensure_program`` holds, or — lazy engines only — one
        isolated AOT lowering."""
        from ..analysis.compiled import program_summary

        tag = (kind, bucket, bool(any_sample))
        s = self._program_analysis.get(tag)
        if s is not None:
            return s
        if self._cc is not None:
            # load-or-compile through the cache: both paths memoize
            # the summary (sidecar read or extract-at-store)
            exe = self._ensure_program(kind, bucket, any_sample)
            s = self._program_analysis.get(tag)
            if s is not None:
                return s
            # artifact predates the analysis sidecar: summarize the
            # live executable once (no re-store; the next cold compile
            # writes the sidecar)
        else:
            exe = self._lower_isolated(kind, bucket, any_sample)
        s = program_summary(exe)
        self._record_summary(kind, bucket, any_sample, s)
        return s

    def check_compiled_programs(self, passes=None):
        """Level-3 analysis over the whole program family: run the
        compiled-program rule set (collective census, per-chip memory
        budget — ``analysis.compiled.COMPILED_PASSES``) over every
        program's summary and return the collected Report. Pure
        collection — callers (:meth:`check_programs`, the build-time
        memory gate) enforce; a crashing pass or an unsummarizable
        program degrades to a warned ``pass-crash`` finding, never an
        exception (the ``analysis.compiled`` fault-site contract)."""
        from .. import analysis
        from ..analysis.compiled import summary_findings

        cfg = self.config
        report = analysis.Report()
        for kind, bucket, any_sample in self._program_tags():
            label = (
                f"serving.{kind}" if bucket is None
                else f"serving.{kind}[{bucket}]"
            )
            try:
                summary = self._program_summary(
                    kind, bucket, any_sample
                )
            except Exception as e:
                # analysis: allow(broad-except) an analyzer compile
                # failure degrades like a crashing pass — L3 must
                # never take down an engine build
                report.add(analysis.Finding(
                    rule="pass-crash",
                    severity=analysis.Severity.WARNING,
                    message=(
                        f"compiled analysis of {label} crashed: {e!r}"
                    ),
                    root=label,
                ))
                continue
            report.extend(summary_findings(
                summary,
                program=label,
                tp_numerics=(
                    cfg.tp_numerics if self.tp is not None else None
                ),
                tp_degree=cfg.tp_degree,
                device_memory_budget=cfg.device_memory_budget,
                mode="collect",
                passes=passes,
            ))
        return report

    def _enforce_memory_budget(self):
        """The build-time memory gate: census the family's predicted
        per-chip peaks against ``EngineConfig(device_memory_budget=)``
        and refuse (``analysis_check=None``/"error") or warn ("warn")
        BEFORE the KV pool exists — a config that would die with
        RESOURCE_EXHAUSTED never allocates its pool."""
        from .. import analysis

        mode = self.config.analysis_check or "error"
        report = self.check_compiled_programs(
            passes=("memory-budget",)
        )
        if self._manifest is not None:
            # the gate may have appended memory= extras after warmup's
            # batched save — persist them for the manifest audit trail
            self._save_manifest()
        analysis.enforce(
            report, mode,
            what=(
                "serving program family under EngineConfig("
                f"device_memory_budget={self.config.device_memory_budget})"
            ),
        )
        return report

    def _check_decode(self, mode="error"):
        """The decode slice of :meth:`check_programs` (level 1): trace
        the decode step over representative inputs and assert it is
        free of host-sync and retrace findings — the serving-loop
        invariant behind the single-compile guarantee, checked WITHOUT
        executing anything. Returns the full analysis Report."""
        from .. import analysis

        if mode not in ("warn", "error"):
            raise ValueError(
                f'check_decode mode must be "warn" or "error", got '
                f"{mode!r}"
            )
        self._pin_adapter()
        cfg = self.config
        n = cfg.max_batch_slots
        params = pack_sampling_params(self.slots)
        m = self.metrics
        saved = (m.prefill_compiles, m.decode_compiles)
        report = analysis.Report()
        try:
            # trace-only: restore the traced-body compile probes after,
            # so an analysis trace never reads as a real (re)compile
            # (the harness isolates the pjit cache, so the real warmup
            # launch still traces — and counts — normally). BOTH static
            # program variants are gated: greedy-only (any_sample=False)
            # and mixed-sampling (True) — a hazard inside the sampling
            # warp must not wait for the first do_sample request.
            seen = set()
            for any_sample in (False, True):
                do_sample = (
                    np.ones(n, bool) if any_sample
                    else params["do_sample"]
                )
                variant = analysis.check(
                    self._decode_fn,
                    self._launch_weights(), self.pool.k, self.pool.v,
                    np.zeros(n, np.int32), np.zeros(n, np.int32),
                    np.zeros((n, cfg.pages_per_seq), np.int32),
                    np.zeros(n, bool),
                    params["temperature"], params["top_k"],
                    params["top_p"], do_sample, self._base_key,
                    any_sample,
                    static_argnums=(12,),
                    donate_argnums=(1, 2) if self._pool_donated else (),
                    mode=mode, root="serving.decode",
                )
                for f in variant.findings:
                    key = (f.rule, f.file, f.line, f.message)
                    if key not in seen:  # shared-path findings once
                        seen.add(key)
                        report.add(f)
        finally:
            m.prefill_compiles, m.decode_compiles = saved
        blocking = report.by_rule("host-sync") + report.by_rule(
            "retrace-hazard"
        )
        if blocking:
            msg = (
                "serving decode step failed static analysis (the "
                "single-compile decode invariant):\n"
                + "\n".join(f.render() for f in blocking)
            )
            if mode == "error":
                raise analysis.AnalysisError(msg, report)
            import warnings

            warnings.warn(msg, stacklevel=2)
        return report

    def _check_prefill(self, mode="error"):
        """The prefix-cache / chunked-prefill slice of
        :meth:`check_programs` (level 1): the continuation prefill
        (both static sampling variants) and the COW block copy, held to
        zero host-sync and retrace findings — a chunk launch sits on
        the same latency-critical path as the decode step. Trace-only;
        compile probes are restored after."""
        from .. import analysis

        if mode not in ("warn", "error"):
            raise ValueError(
                f'check_prefill mode must be "warn" or "error", got '
                f"{mode!r}"
            )
        self._pin_adapter()
        cfg = self.config
        bucket = cfg.prefill_buckets[0]
        m = self.metrics
        saved = (m.prefill_compiles, m.decode_compiles,
                 m.prefill_ext_compiles, m.cow_compiles)
        donate = (1, 2) if self._pool_donated else ()
        report = analysis.Report()
        seen = set()

        def merge(variant):
            for f in variant.findings:
                key = (f.rule, f.file, f.line, f.message)
                if key not in seen:  # shared-path findings once
                    seen.add(key)
                    report.add(f)

        try:
            for any_sample in (False, True):
                merge(analysis.check(
                    self._prefill_ext_fn,
                    self._launch_weights(), self.pool.k, self.pool.v,
                    np.zeros(bucket, np.int32), np.int32(1), np.int32(0),
                    np.zeros(cfg.pages_per_seq, np.int32),
                    np.float32(1.0), np.int32(0), np.float32(1.0),
                    np.bool_(any_sample), self._base_key, any_sample,
                    static_argnums=(12,), donate_argnums=donate,
                    mode=mode, root="serving.prefill_ext",
                ))
            if cfg.enable_prefix_cache:
                merge(analysis.check(
                    self._cow_fn, self.pool.k, self.pool.v,
                    np.int32(0), np.int32(1),
                    donate_argnums=(0, 1) if self._pool_donated else (),
                    mode=mode, root="serving.cow",
                ))
        finally:
            (m.prefill_compiles, m.decode_compiles,
             m.prefill_ext_compiles, m.cow_compiles) = saved
        blocking = report.by_rule("host-sync") + report.by_rule(
            "retrace-hazard"
        )
        if blocking:
            msg = (
                "serving prefill continuation failed static analysis "
                "(the chunked-prefill latency invariant):\n"
                + "\n".join(f.render() for f in blocking)
            )
            if mode == "error":
                raise analysis.AnalysisError(msg, report)
            import warnings

            warnings.warn(msg, stacklevel=2)
        return report

    def _check_verify(self, mode="error"):
        """The speculative-VERIFY slice of :meth:`check_programs`
        (level 1): the draft-window scoring step, held to zero
        host-sync and retrace findings — a verify launch replaces the
        decode launch on the latency-critical greedy path. Trace-only;
        compile probes are restored after."""
        from .. import analysis

        if mode not in ("warn", "error"):
            raise ValueError(
                f'check_verify mode must be "warn" or "error", got '
                f"{mode!r}"
            )
        self._pin_adapter()
        cfg = self.config
        if cfg.speculate_tokens is None:
            raise RuntimeError(
                "check_verify needs EngineConfig(speculate_tokens=): "
                "this engine has speculation disabled"
            )
        n, k = cfg.max_batch_slots, cfg.speculate_tokens
        m = self.metrics
        saved = (m.prefill_compiles, m.decode_compiles,
                 m.verify_compiles)
        try:
            report = analysis.check(
                self._verify_fn,
                self._launch_weights(), self.pool.k, self.pool.v,
                np.zeros((n, k + 1), np.int32), np.zeros(n, np.int32),
                np.zeros(n, np.int32),
                np.zeros((n, cfg.pages_per_seq), np.int32),
                np.zeros(n, bool),
                donate_argnums=(1, 2) if self._pool_donated else (),
                mode=mode, root="serving.verify",
            )
        finally:
            (m.prefill_compiles, m.decode_compiles,
             m.verify_compiles) = saved
        blocking = report.by_rule("host-sync") + report.by_rule(
            "retrace-hazard"
        )
        if blocking:
            msg = (
                "serving verify step failed static analysis (the "
                "speculative-decode latency invariant):\n"
                + "\n".join(f.render() for f in blocking)
            )
            if mode == "error":
                raise analysis.AnalysisError(msg, report)
            import warnings

            warnings.warn(msg, stacklevel=2)
        return report

    def _launch_weights(self):
        """The weight tree every launch (and trace/abstraction site)
        passes to the compiled programs: the engine's own mesh-placed
        copy under TP, the adapter's live tree otherwise — so
        ``adapter.refresh()`` keeps propagating to single-chip engines
        while a sharded engine's placement can never leak through a
        shared adapter."""
        return (
            self._tp_weights if self._tp_weights is not None
            else self.adapter.weights
        )

    def _pin_adapter(self):
        """Re-assert THIS engine's mutable adapter knobs before any
        launch or trace. The traced bodies read ``adapter.tp_spec`` /
        ``adapter.decode_kernel`` at TRACE time, and tracing is lazy
        (first launch, or a mid-serving `_ensure_program` miss) — so a
        shared adapter whose knobs a LATER engine build reassigned
        would otherwise leak that engine's mesh/kernel into this one's
        first trace (exact-mode constraints silently dropped, or a
        single-chip program compiled against another engine's mesh).
        Two attribute writes per launch; already-compiled programs
        never re-read them."""
        if hasattr(self.adapter, "decode_kernel"):
            self.adapter.decode_kernel = self._decode_kernel
        if hasattr(self.adapter, "tp_spec"):
            self.adapter.tp_spec = self.tp

    def _next_key(self):
        self._key_counter += 1
        return jax.random.fold_in(self._base_key, self._key_counter)

    def _request_key(self, req):
        """PRNG key for a single-request launch (prefill / final
        chunk). The engine stream ALWAYS advances — a seeded request in
        the mix never shifts other requests' keys — but a sampled
        request carrying an explicit ``SamplingParams.seed`` draws
        ``fold_in(PRNGKey(seed), n_generated)`` instead: its first
        token is reproducible across restarts, journal replays, and
        failovers regardless of engine history. Batched decode keeps
        the shared per-step stream (docs/serving.md caveat)."""
        key = self._next_key()
        p = req.sampling_params
        if p.do_sample and p.seed is not None:
            return jax.random.fold_in(
                jax.random.PRNGKey(p.seed), len(req.output_token_ids)
            )
        return key

    # -- client API ----------------------------------------------------------
    def add_request(self, prompt_token_ids, sampling_params=None,
                    request_id=None):
        return self.submit(
            Request(prompt_token_ids, sampling_params, request_id)
        )

    def submit(self, req):
        """Admission over a caller-constructed Request — what
        ``add_request`` wraps. Split out so a router (``serving.fleet``)
        can keep ONE Request object across replicas: the same object it
        submits here is what it hands to another replica's
        :meth:`resume` after a failover, tokens intact."""
        cfg = self.config
        if (cfg.max_waiting is not None
                and len(self.waiting) >= cfg.max_waiting):
            raise RuntimeError(
                f"admission queue full ({cfg.max_waiting} waiting)"
            )
        if len(req.prompt_token_ids) >= cfg.max_model_len:
            raise ValueError(
                f"prompt of {len(req.prompt_token_ids)} tokens leaves no "
                f"room to generate under max_model_len={cfg.max_model_len}"
            )
        if cfg.kv_shed_threshold is not None:
            bm = self.block_manager
            reclaimable, util = self._active_pressure()
            admissible_now = (
                not self.waiting and None in self.slots
                and bm.num_free + reclaimable >= bm.blocks_needed(
                    len(req.prompt_token_ids) + 1
                )
            )
            if util >= cfg.kv_shed_threshold and not admissible_now:
                self.metrics.requests_shed += 1
                # generate()'s internal admission retries undo the shed
                # count (flow control, not a rejection) — they must not
                # flood the bounded flight ring either
                if not getattr(self, "_suppress_shed_events", False):
                    _flight.record(
                        "serving", "shed", engine=self.engine_id,
                        request_id=req.request_id, kv_utilization=util,
                        tenant=getattr(req, "tenant", None),
                    )
                raise EngineOverloadedError(
                    f"KV pool at {util:.0%} utilization (threshold "
                    f"{cfg.kv_shed_threshold:.0%}); request shed"
                )
        self.waiting.append(req)
        self.metrics.requests_received += 1
        if self.journal is not None and not self._journal_replaying:
            # WAL the admission (buffered urgent; the next step's group
            # flush makes it durable BEFORE any of its tokens can — an
            # admission is only actionable through step() anyway). The
            # fleet front door flushes per admission instead.
            self.journal.admit(req)
        return req

    def _active_pressure(self):
        """``(reclaimable_blocks, active_utilization)`` — the pressure
        split every consumer (shedding, health, metrics gauges) must
        agree on: cached prefix blocks nobody runs against and idle
        speculative draft headroom are RECLAIMABLE capacity, not
        pressure, so a pool kept warm by the prefix cache (or padded
        by draft headroom) neither sheds admissions nor reads as
        overloaded."""
        bm = self.block_manager
        reclaimable = (
            self.prefix_cache.reclaimable_blocks()
            if self.prefix_cache is not None else 0
        )
        if self._speculating:
            reclaimable += sum(
                self._spec_headroom(r) for r in self.slots
            )
        return reclaimable, (bm.num_used - reclaimable) / bm.num_blocks

    def _spec_headroom(self, req):
        """Idle draft-headroom blocks a greedy RUNNING slot holds
        beyond its required ``num_cached + 1`` coverage (0 for every
        other slot) — THE shared definition behind pressure accounting
        (:meth:`_active_pressure`) and reclaim
        (:meth:`_reclaim_spec_headroom`); they must agree or admission
        would see capacity reclaim cannot actually deliver."""
        if (req is None or req.state is not RequestState.RUNNING
                or req.sampling_params.do_sample):
            return 0
        return max(
            len(req.block_ids)
            - self.block_manager.blocks_needed(req.num_cached + 1), 0,
        )

    def resume(self, req):
        """Re-enqueue a request whose KV state was lost OUTSIDE the
        engine's control — a fleet failover hands a dead replica's
        in-flight Request to a healthy engine here. The externally
        driven form of recompute preemption: scheduling state is reset,
        prompt and already-generated tokens are kept, so the next
        prefill rebuilds the cache over ``prompt + output[:-1]`` and
        greedy continuation is bit-identical to an uninterrupted run.
        Joins the HEAD of the queue (it has been waiting longest) and
        deliberately bypasses ``max_waiting``/shedding: recovered work
        must not be dropped by admission control."""
        if req.state is RequestState.FINISHED:
            raise ValueError(
                f"cannot resume finished request {req.request_id!r}"
            )
        req.block_ids = []
        req.num_cached = 0
        req.slot = None
        req.state = RequestState.WAITING
        # goodput attribution: the re-prefill recomputes context built
        # on another replica — migration waste, not preemption
        req.resume_cause = "migration"
        self.waiting.appendleft(req)
        self.metrics.requests_received += 1
        req.timeline.resumes += 1
        if self.journal is not None and not self._journal_replaying:
            # re-ADMIT with the emit cursor: replay must not re-count
            # the tokens this request already produced elsewhere
            self.journal.admit(req)
        return req

    def abort(self, request_id):
        """Drop a request wherever it is; returns True if found. The
        abort goes through the normal finish accounting (finish_time,
        ``requests_finished``, a RequestOutput with
        ``finish_reason="aborted"`` emitted by the NEXT ``step()``), so
        drivers blocked on the request's completion — ``generate()``,
        a fleet drain — observe it instead of waiting forever. Aborts
        are not failures (no error probe, no postmortem dump), but the
        request's timeline still lands in the flight timeline ring and
        the access log — excluded from the finish-time latency
        digests/SLO window (see docs/observability.md)."""
        for req in list(self.waiting):
            if req.request_id == request_id:
                self.waiting.remove(req)
                self._finish(req, "aborted", self._aborted)
                return True
        for req in self.slots:
            if req is not None and req.request_id == request_id:
                self._finish(req, "aborted", self._aborted)
                return True
        return False

    def release(self, request_id):
        """Detach an unfinished request from this engine WITHOUT
        finishing it — the fleet's migration primitive (scale-down,
        rolling restart). KV blocks and the slot are freed, scheduling
        state resets to WAITING with ``num_cached=0``, and the Request
        object — prompt, generated tokens, tenant tag, arrival/deadline
        clocks — is returned intact for :meth:`resume` on another
        replica (re-prefill over ``prompt + output[:-1]``; greedy
        continuation byte-identical). No finish accounting, no
        RequestOutput: from the caller's point of view the request is
        still in flight, just homeless. Returns None when the id is not
        here or already finished."""
        req = None
        for r in list(self.waiting):
            if r.request_id == request_id:
                self.waiting.remove(r)
                req = r
                break
        if req is None:
            for r in self.slots:
                if r is not None and r.request_id == request_id:
                    req = r
                    break
        if req is None or req.state is RequestState.FINISHED:
            return None
        # same-host migration rides the spill tier: park the cached
        # blocks under a handle the SURVIVOR's admission can restore
        # (tiers cross-lookup within the process; the handle key rides
        # the Request and the fleet's re-ADMIT journal record). A
        # cross-host resume simply misses and re-prefills as before.
        self._spill_request(req)
        self._release(req)
        req.state = RequestState.WAITING
        req.num_cached = 0
        return req

    def has_unfinished(self):
        return bool(self._aborted) or bool(self.waiting) or any(
            r is not None for r in self.slots
        )

    def generate(self, prompts, sampling_params=None):
        """Convenience driver: submit everything, step until drained,
        return RequestOutputs in submission order. ``sampling_params`` may
        be one SamplingParams for all prompts or a list per prompt.
        Submission respects ``max_waiting`` by feeding the queue as it
        drains instead of raising mid-batch."""
        params = normalize_sampling_params(prompts, sampling_params)
        cap = self.config.max_waiting
        pending = collections.deque(zip(prompts, params))
        reqs, done = [], {}
        stalls = 0
        while pending or self.has_unfinished():
            admitted = False
            while pending and (cap is None or len(self.waiting) < cap):
                p, sp = pending.popleft()
                try:
                    self._suppress_shed_events = True
                    try:
                        reqs.append(self.add_request(p, sp))
                        admitted = True
                    finally:
                        self._suppress_shed_events = False
                except EngineOverloadedError:
                    # flow control, not a caller-visible rejection: the
                    # prompt is resubmitted once the batch drains, so
                    # undo the shed count the internal retry incurred
                    self.metrics.requests_shed -= 1
                    pending.appendleft((p, sp))
                    break
            outs = self.step()
            for out in outs:
                done[out.request_id] = out
            if (pending and not admitted and not outs
                    and not self.has_unfinished()):
                # every prompt shed with nothing in flight: step() is a
                # no-op, so spinning on it burns a core without moving
                # the pressure — back off (exponential + jitter) until
                # admission clears
                stalls += 1
                self._shed_backoff.pause(stalls + 1)
            else:
                stalls = 0
        return [done[r.request_id] for r in reqs]

    # -- scheduler -----------------------------------------------------------
    def step(self):
        """One scheduler iteration: expire TTLs, admit + prefill
        joiners, then one decode step over the occupied slots. Returns
        RequestOutputs for requests that finished during this step.

        Failure containment: a request whose prefill or decode raises is
        finished with ``finish_reason="error"`` (the exception recorded
        on ``RequestOutput.error``) while the engine keeps stepping the
        remaining requests — one poison request cannot take down the
        batch. Comm-watchdog aborts are NOT contained: a cluster-level
        abort must propagate. Anything that does escape (watchdog
        abort, donated-pool loss) dumps the flight recorder with this
        engine's health snapshot on the way out — the engine is about
        to die, so leave the postmortem."""
        finished: list = []
        if self._aborted:
            # requests aborted since the last step finish HERE (see
            # abort()): their slots/blocks were already released
            finished.extend(self._aborted)
            self._aborted.clear()
        if self.stepstats is not None:
            self.stepstats.begin_step()
        try:
            self._expire(finished)
            self._admit(finished)
            self._prefill_chunks(finished)
            running = RequestState.RUNNING
            if any(r is not None and r.state is running
                   for r in self.slots):
                self._ensure_capacity()
                if any(r is not None and r.state is running
                       for r in self.slots):
                    self._decode(finished)
        except Exception as e:
            _flight.record(
                "serving", "engine-error", engine=self.engine_id,
                error=f"{type(e).__name__}: {e}",
            )
            # the engine is broken by definition here — health() itself
            # may raise over torn state, and nothing on the postmortem
            # path may displace the exception we are re-raising
            try:
                probe = self.health()
            except Exception as he:
                probe = {"error": f"health() failed: {he!r}"}
            _flight.dump(
                "engine-error",
                probes={f"serving.engine.{self.engine_id}": probe},
            )
            raise
        if self.journal is not None:
            # batched EMIT + group write (finished requests already
            # buffered theirs in _finish). Steady-state steps are a
            # near-no-op: tokens batch on the Request objects until
            # the write interval elapses or a completion makes the
            # buffer urgent — a lost interval's tokens are re-derived
            # byte-identically by replay's recompute.
            self.journal.step_flush(self.slots)
        m, bm = self.metrics, self.block_manager
        m.queue_depth = len(self.waiting)
        m.num_running = sum(r is not None for r in self.slots)
        m.cache_utilization = bm.utilization()
        m.kv_reclaimable_blocks, m.kv_active_utilization = (
            self._active_pressure()
        )
        if self.prefix_cache is not None:
            m.prefix_cache_blocks = len(self.prefix_cache)
        m.pool_high_water = bm.high_water
        m.kv_headroom_blocks = bm.num_free + m.kv_reclaimable_blocks
        st = self.stepstats
        if st is not None:
            try:
                faults.fire("obs.stepstats", engine=self.engine_id)
                sample = st.end_step(
                    occupancy=(
                        m.num_running / self.config.max_batch_slots
                    ),
                    queue_depth=m.queue_depth,
                    kv_free_blocks=bm.num_free,
                    kv_reclaimable_blocks=m.kv_reclaimable_blocks,
                )
                if sample is not None:
                    # the flight recorder's bounded step-sample ring:
                    # a postmortem shows the last N steps' attribution
                    _flight.record_step_sample(
                        dict(sample, engine=self.engine_id)
                    )
            except Exception as e:  # analysis: allow(broad-except)
                # degradable by contract: the observatory must never
                # take the step down with it
                self._disable_stepstats(e)
        return finished

    def health(self):
        """One-call health snapshot (scrape-endpoint / watchdog probe /
        fleet router): ``status`` is "ok", "degraded" (poisoned/expired
        requests or a tripped comm watchdog), or "overloaded"
        (admission queue full or KV pressure at the shedding
        threshold). ``status`` keeps its single-string precedence
        (overloaded beats degraded) for back-compat; ``flags`` carries
        BOTH signals independently — the fleet router gates admission
        on it, where overloaded-masking-degraded would hide a sick
        replica behind a busy one."""
        m, bm, cfg = self.metrics, self.block_manager, self.config
        wd = get_comm_watchdog()
        util = bm.utilization()
        # pressure is judged on ACTIVE utilization (_active_pressure):
        # reclaimable cached prefix blocks are capacity the engine can
        # take back at will, not an overloaded replica
        reclaimable, util_active = self._active_pressure()
        queue_full = (
            cfg.max_waiting is not None
            and len(self.waiting) >= cfg.max_waiting
        )
        shedding = (
            cfg.kv_shed_threshold is not None
            and util_active >= cfg.kv_shed_threshold
        )
        # sustained SLO error-budget burn degrades the replica so an
        # external load balancer rotates it out (503 via /healthz).
        # The in-process fleet router deliberately does NOT unroute on
        # it (supervisor.routable gates on overload/fresh errors):
        # serving slowly beats not serving, and unrouting every slow
        # replica at once would turn a latency incident into an outage
        slo_burning = self.slo is not None and self.slo.burning()
        degraded = bool(
            m.requests_errored or m.requests_timeout or slo_burning
            or (wd is not None and wd.fired is not None)
        )
        overloaded = queue_full or shedding
        status = "ok"
        if degraded:
            status = "degraded"
        if overloaded:
            status = "overloaded"
        return {
            "status": status,
            "flags": [
                f for f, on in (
                    ("degraded", degraded), ("overloaded", overloaded),
                    ("slo_burn", slo_burning),
                ) if on
            ],
            # windowed error-budget burn per signal (None = no SLO /
            # no samples); burn 1.0 = spending the budget as allotted
            "slo_burn_rates": (
                self.slo.burn_rates() if self.slo is not None else None
            ),
            "queue_depth": len(self.waiting),
            "num_running": sum(r is not None for r in self.slots),
            # kernel-path observability: which decode attention path
            # this engine was configured with and what the KV pool
            # stores (degradations are visible in the process-wide
            # paddle_tpu_kernels_fallbacks_total counter)
            "decode_kernel": cfg.decode_kernel,
            # the path programs were actually built with (a sharded
            # engine's "pallas"/"auto" resolves to the XLA gather path)
            "decode_kernel_effective": self._decode_kernel,
            # tensor parallelism: degree + mesh device ids, so /healthz
            # and the fleet router can tell a 4-chip replica from a
            # 1-chip one
            "tp_degree": cfg.tp_degree,
            "tp_numerics": (
                cfg.tp_numerics if self.tp is not None else None
            ),
            "tp_devices": (
                self.tp.device_ids if self.tp is not None else []
            ),
            "kv_cache_dtype": cfg.kv_cache_dtype or str(
                self.pool._dtype
            ),
            "kv_bytes_per_token": self.pool.bytes_per_token(),
            "kv_bytes_per_token_per_chip": (
                self.pool.bytes_per_token_per_chip()
            ),
            # the L3 memory gate's view: the configured per-chip byte
            # budget (None = gate off) and the largest predicted
            # per-chip peak across the analyzed program family (None
            # until any program has been summarized — lazy engines
            # without the gate never pay for the prediction)
            "device_memory_budget": cfg.device_memory_budget,
            "predicted_peak_bytes_per_chip": (
                max(self.metrics.program_bytes.values())
                if self.metrics.program_bytes else None
            ),
            "kv_utilization": util,
            "kv_active_utilization": util_active,
            "kv_reclaimable_blocks": reclaimable,
            # headroom the router weighs: blocks this replica could
            # still absorb (free + reclaimable), plus the per-chip
            # byte view so heterogeneous-width slices compare fairly
            "kv_headroom_blocks": bm.num_free + reclaimable,
            "kv_headroom_bytes_per_chip": int(
                (bm.num_free + reclaimable)
                * self.pool.block_bytes_per_chip()
            ),
            # step observatory summary (None = sampler disabled):
            # per-program step walls, goodput ledger, occupancy, MFU
            "stepstats": (
                self.stepstats.summary()
                if self.stepstats is not None else None
            ),
            "prefix_cache_blocks": (
                len(self.prefix_cache)
                if self.prefix_cache is not None else 0
            ),
            # cached chain keys (wire form): a fleet router matches a
            # request's prompt digests against these to find the
            # replica already holding its prefix (hit-aware routing)
            "prefix_cache_digests": (
                self.prefix_cache.chain_digests()
                if self.prefix_cache is not None else []
            ),
            # host spill tier (serving/spill.py): occupancy, per-class
            # spilled/restored traffic, restore hit rate — None when
            # the tier is disabled (host_spill_bytes unset)
            "spill": (
                self.spill.stats() if self.spill is not None else None
            ),
            # speculation economics: accepted / proposed draft tokens
            # (None until the first proposal)
            "spec_accept_rate": m.spec_accept_rate,
            "requests_errored": m.requests_errored,
            "requests_timeout": m.requests_timeout,
            "requests_shed": m.requests_shed,
            "preemptions": m.preemptions,
            "last_error": m.last_error,
            "watchdog": {
                "enabled": wd is not None,
                "fired": None if wd is None else wd.fired,
            },
        }

    def _expire(self, finished):
        """Finish requests (queued or running) whose TTL has lapsed with
        finish_reason="timeout"."""
        now = time.perf_counter()
        for req in [r for r in self.waiting if r.expired(now)]:
            self.waiting.remove(req)
            self.metrics.requests_timeout += 1
            self._finish(req, "timeout", finished)
        for req in list(self.slots):
            if req is not None and req.expired(now):
                self.metrics.requests_timeout += 1
                self._finish(req, "timeout", finished)

    def _poison(self, req, exc, finished):
        """Contain a per-request failure: record it, finish the request
        with an error, keep the engine stepping."""
        req.error = f"{type(exc).__name__}: {exc}"
        m = self.metrics
        m.requests_errored += 1
        m.last_error = f"request {req.request_id}: {req.error}"
        self._finish(req, "error", finished)

    def _admit(self, finished):
        """FCFS admission into free slots. A request is admitted with
        its FULL block budget (whole prompt plus one decode write) but
        no compute: the prefix cache may cover a prefix via ``fork()``
        (copy-on-write when the one-token cap cuts into the last shared
        block), and the actual prefill runs in :meth:`_prefill_chunks`
        — one launch, or several interleaved chunk launches."""
        bm = self.block_manager
        while self.waiting and None in self.slots:
            req = self.waiting[0]
            tokens = req.tokens_to_prefill()
            # restore-instead-of-recompute: a preempted/released
            # request carrying a live spill handle skips the prefix
            # lookup — its OWN cached blocks come back from the host
            # tier (full block budget still allocated below)
            restore_tokens = self._spill_restorable(req, tokens)
            match = None
            if restore_tokens is None and self.prefix_cache is not None:
                # at least one token must remain to prefill: its logits
                # seed the first sampled token
                match = self.prefix_cache.lookup(
                    tokens, limit=len(tokens) - 1
                )
            n_fork = match.num_shared if match is not None else 0
            n_alloc = bm.blocks_needed(len(tokens) + 1) - n_fork
            if not bm.can_allocate(n_alloc):
                if self.prefix_cache is not None:
                    # retained cache blocks are reclaimable capacity —
                    # but never the ones this very match is about to
                    # fork or copy from
                    protect = set(
                        match.shared_blocks
                    ) if match is not None else set()
                    if match is not None and match.cow_src is not None:
                        protect.add(match.cow_src)
                    self.prefix_cache.reclaim(
                        n_alloc - bm.num_free, protect=protect
                    )
                if not bm.can_allocate(n_alloc):
                    # idle draft headroom is reclaimable capacity too:
                    # an admission must never be refused while
                    # speculation holds unused blocks
                    self._reclaim_spec_headroom(n_alloc - bm.num_free)
                if not bm.can_allocate(n_alloc):
                    break
            self.waiting.popleft()
            if restore_tokens is None and self.prefix_cache is not None:
                # one lookup per ADMISSION (blocked retries don't count;
                # neither do they touch the LRU — see lookup/commit)
                self.metrics.prefix_lookups += 1
            if restore_tokens is not None:
                req.block_ids = bm.allocate(n_alloc)
                # a failed restore keeps the blocks and recomputes:
                # num_cached=0 sends the whole prompt back through
                # prefill — exactly the pre-spill preemption path
                req.num_cached = (
                    restore_tokens
                    if self._spill_restore(req, restore_tokens) else 0
                )
            elif match is not None:
                bm.fork(match.shared_blocks)
                req.block_ids = list(match.shared_blocks) + bm.allocate(
                    n_alloc
                )
                req.num_cached = match.cache_len
                self.prefix_cache.commit(match)
            else:
                req.block_ids = bm.allocate(n_alloc)
                req.num_cached = 0
            req.slot = self.slots.index(None)
            self.slots[req.slot] = req
            req.state = RequestState.PREFILLING
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            # timeline: queue wait ends at the FIRST slot assignment
            # (re-admissions after preemption keep the original stamp;
            # the hop list tracks which engines admitted it)
            tl = req.timeline
            first_admission = tl.admitted is None
            tl.mark_admitted(self.engine_id)
            if first_admission:
                self.metrics.latency["queue"].record(tl.queue_wait_s)
            if match is not None:
                tl.prefix_hit_tokens += match.cache_len
            if match is not None and match.cow_src is not None:
                # the cap cut into the last shared block: this request
                # will WRITE its final prefill token there, so it gets
                # a private copy (block index n_fork is freshly
                # allocated) instead of a fork
                try:
                    self._cow(match.cow_src, req.block_ids[n_fork])
                except CommTimeoutError:
                    raise  # cluster-level abort, not a poison request
                except Exception as e:
                    if getattr(e, "_kv_pool_unsafe", False):
                        raise  # donated pool may be gone
                    self._poison(req, e, finished)
                    continue
            if (restore_tokens is not None
                    and req.num_cached >= len(tokens)):
                # fully-covered restore: the cache already holds
                # prompt + output[:-1], exactly the pre-preemption
                # decode state — no prefill launch at all. Straight to
                # RUNNING with the last token re-armed; the goodput
                # ledger's preempt_recompute class books ZERO tokens.
                req.state = RequestState.RUNNING
                req.last_token = req.output_token_ids[-1]
                if self.prefix_cache is not None:
                    # publish the restored PROMPT blocks for reuse,
                    # mirroring the post-prefill register
                    self.prefix_cache.register(
                        req.prompt_token_ids, req.block_ids,
                        req.num_cached,
                    )
                reason = req.check_stop(self.config.max_model_len)
                if reason:
                    self._finish(req, reason, finished)

    def _disable_stepstats(self, exc):
        """``obs.stepstats`` degradation: a crashing sampler is warned
        ONCE and dropped — its collector view unregisters through the
        weakref at the next scrape — and serving continues without the
        observatory. The step itself must never pay for a sampler
        failure."""
        if not self._stepstats_warned:
            self._stepstats_warned = True
            warnings.warn(
                f"step observatory disabled for engine "
                f"{self.engine_id} after sampler failure: "
                f"{type(exc).__name__}: {exc}",
                RuntimeWarning, stacklevel=2,
            )
        self.stepstats = None

    def _stepstats_launch(self, program, t0):
        """Record one device launch wall for the observatory. ``t0``
        was taken immediately before the launch block, whose body ends
        with the host-side sync — so the wall is device-inclusive
        block-until-ready time, with zero effect on traced code."""
        st = self.stepstats
        if st is None:
            return
        try:
            st.record_launch(program, time.perf_counter() - t0)
        except Exception as e:  # analysis: allow(broad-except) degradable
            self._disable_stepstats(e)

    def _watch(self, tag):
        """Hung-step detection: launches run under the comm watchdog
        when one is enabled (serving's analogue of watchdog-tracked
        collectives)."""
        wd = get_comm_watchdog()
        if wd is None:
            import contextlib

            return contextlib.nullcontext()
        return wd.watch(tag)

    def _prefill(self, req, tokens):
        self._pin_adapter()
        faults.fire(
            "serving.step", phase="prefill", request_id=req.request_id,
        )
        cfg = self.config
        bucket = next_bucket(len(tokens), cfg.prefill_buckets)
        ids = np.zeros(bucket, np.int32)
        ids[: len(tokens)] = tokens
        table = np.zeros(cfg.pages_per_seq, np.int32)
        table[: len(req.block_ids)] = req.block_ids
        p = req.sampling_params
        _t0 = time.perf_counter()
        with span(
            "serving.prefill", request_id=req.request_id, bucket=bucket,
        ), self._watch("serving.prefill"), jit_events.watch(
            # engine id in the signature: a SECOND engine compiling its
            # own programs is a fresh compile, not a retrace alarm —
            # and any_sample is a static compile key (same as decode's
            # signature), so the first sampled request on a warm bucket
            # is a fresh variant, not a retrace
            "serving.prefill", kind="serving",
            signature=(f"{self.engine_id}:bucket={bucket}"
                       f":any_sample={bool(p.do_sample)}"),
        ):
            try:
                args = (
                    self._launch_weights(), self.pool.k, self.pool.v,
                    ids, np.int32(len(tokens)), table,
                    np.float32(p.temperature), np.int32(p.top_k),
                    np.float32(p.top_p), np.bool_(p.do_sample),
                    self._request_key(req),
                )
                if self._cc is not None:
                    # compile-cache mode: launch the AOT executable
                    # (loaded from disk or compiled once at warmup) —
                    # the static any_sample flag is baked into it
                    exe = self._ensure_program(
                        "prefill", bucket=bucket,
                        any_sample=bool(p.do_sample),
                    )
                    tok, k, v = exe(*args)
                else:
                    tok, k, v = self._prefill_jit(
                        *args, bool(p.do_sample)
                    )
            except Exception as e:
                # same donated-buffer hazard as decode (_launch_decode):
                # a dispatched-program failure may have consumed the
                # donated pool, so containment must not continue over it
                if self._pool_donated:
                    e._kv_pool_unsafe = True
                raise
            tok = int(tok)
        self._stepstats_launch("prefill", _t0)
        self.pool.rebind(k, v)
        req.num_cached = len(tokens)
        self.metrics.prefill_tokens += len(tokens)
        self.metrics.prefill_steps += 1
        req.timeline.prefill_chunks += 1
        req.timeline.prefill_tokens += len(tokens)
        st = self.stepstats
        if st is not None:
            # goodput: a re-prefill over already-produced context
            # (output tokens exist) recomputes, attributed to the
            # preemption or migration that forced it
            st.note_prefill(
                len(tokens),
                cause=(req.resume_cause or "preempt")
                if req.output_token_ids else None,
            )
        self._finish_prefill(req, tok)

    def _finish_prefill(self, req, tok):
        """Book the first token once a request's whole prefill has
        landed (one-shot or final chunk)."""
        if req.output_token_ids:
            # resumed after preemption: the sampled token re-derives
            # output[-1]; keep the one we already have
            req.last_token = req.output_token_ids[-1]
        else:
            req.first_token_time = time.perf_counter()
            req.timeline.first_token = req.first_token_time
            self.metrics.record_ttft(
                req.first_token_time - req.arrival_time
            )
            req.output_token_ids.append(tok)
            req.last_token = tok

    def _prefill_chunks(self, finished):
        """Run prefill launches for PREFILLING slot occupants, oldest
        first. Chunking disabled: every pending prefill completes this
        step (one launch each — the pre-chunking behavior). Chunking
        enabled: at most ``max_prefill_chunks_per_step`` chunk launches
        run, then the decode batch gets the step — a long prompt is
        spread over steps instead of stalling every running request."""
        cfg = self.config
        budget = (
            cfg.max_prefill_chunks_per_step if self._chunking else None
        )
        used = 0
        for req in sorted(
            (r for r in self.slots
             if r is not None and r.state is RequestState.PREFILLING),
            key=lambda r: r.admit_seq,
        ):
            while req.state is RequestState.PREFILLING:
                if budget is not None and used >= budget:
                    return
                used += 1
                tokens = req.tokens_to_prefill()
                remaining = tokens[req.num_cached:]
                chunk = (
                    remaining[:cfg.prefill_chunk_tokens]
                    if self._chunking else remaining
                )
                final = req.num_cached + len(chunk) >= len(tokens)
                try:
                    if req.num_cached == 0 and final:
                        # nothing cached, everything fits: the classic
                        # one-shot program (bit-for-bit today's path)
                        self._prefill(req, tokens)
                    else:
                        self._prefill_chunk(req, chunk, final)
                except CommTimeoutError:
                    raise  # cluster-level abort, not a poison request
                except Exception as e:
                    if getattr(e, "_kv_pool_unsafe", False):
                        raise  # donated pool may be gone
                    self._poison(req, e, finished)
                    break
                if final:
                    req.state = RequestState.RUNNING
                    if self.prefix_cache is not None:
                        # publish the full PROMPT blocks for reuse
                        # (decode never writes them again: writes only
                        # land at positions >= the prompt length)
                        self.prefix_cache.register(
                            req.prompt_token_ids, req.block_ids,
                            req.num_cached,
                        )
                    reason = req.check_stop(cfg.max_model_len)
                    if reason:
                        self._finish(req, reason, finished)

    def _prefill_chunk(self, req, chunk, final):
        """One continuation launch: ``chunk`` tokens appended at cache
        position ``req.num_cached`` through the PREFILL_EXT program.
        Non-final chunks run the greedy-only variant regardless of the
        request's sampling params — their sampled token is discarded,
        so the vocab warp would be wasted compute."""
        self._pin_adapter()
        faults.fire(
            "serving.step", phase="prefill", request_id=req.request_id,
        )
        cfg = self.config
        bucket = next_bucket(len(chunk), cfg.prefill_buckets)
        ids = np.zeros(bucket, np.int32)
        ids[: len(chunk)] = chunk
        table = np.zeros(cfg.pages_per_seq, np.int32)
        table[: len(req.block_ids)] = req.block_ids
        p = req.sampling_params
        cache_len = req.num_cached
        any_sample = bool(p.do_sample) and final
        _t0 = time.perf_counter()
        with span(
            "serving.prefill_ext", request_id=req.request_id,
            bucket=bucket, cache_len=cache_len,
        ), self._watch("serving.prefill"), jit_events.watch(
            "serving.prefill_ext", kind="serving",
            signature=(f"{self.engine_id}:bucket={bucket}"
                       f":any_sample={any_sample}"),
        ):
            try:
                args = (
                    self._launch_weights(), self.pool.k, self.pool.v,
                    ids, np.int32(len(chunk)), np.int32(cache_len),
                    table,
                    np.float32(p.temperature), np.int32(p.top_k),
                    np.float32(p.top_p), np.bool_(p.do_sample),
                    self._request_key(req),
                )
                if self._cc is not None:
                    exe = self._ensure_program(
                        "prefill_ext", bucket=bucket,
                        any_sample=any_sample,
                    )
                    tok, k, v = exe(*args)
                else:
                    tok, k, v = self._prefill_ext_jit(*args, any_sample)
            except Exception as e:
                # same donated-buffer hazard as decode (_launch_decode)
                if self._pool_donated:
                    e._kv_pool_unsafe = True
                raise
            if final:
                tok = int(tok)
        self._stepstats_launch("prefill_ext", _t0)
        self.pool.rebind(k, v)
        req.num_cached = cache_len + len(chunk)
        self.metrics.prefill_tokens += len(chunk)
        self.metrics.prefill_steps += 1
        self.metrics.prefill_chunks += 1
        req.timeline.prefill_chunks += 1
        req.timeline.prefill_tokens += len(chunk)
        st = self.stepstats
        if st is not None:
            # same recompute attribution as _prefill: every chunk of a
            # resumed request rebuilds cache it already had
            st.note_prefill(
                len(chunk),
                cause=(req.resume_cause or "preempt")
                if req.output_token_ids else None,
            )
        if final:
            self._finish_prefill(req, tok)

    def _cow(self, src, dst):
        """Copy-on-write one physical block (every layer's pages) so a
        prefill can diverge from a shared partial block without
        touching the original."""
        self._pin_adapter()
        _t0 = time.perf_counter()
        with span(
            "serving.cow", src=int(src), dst=int(dst),
        ), self._watch("serving.cow"), jit_events.watch(
            "serving.cow", kind="serving", signature=self.engine_id,
        ):
            try:
                args = (
                    self.pool.k, self.pool.v, np.int32(src),
                    np.int32(dst),
                )
                if self._cc is not None:
                    exe = self._ensure_program("cow")
                    k, v = exe(*args)
                else:
                    k, v = self._cow_jit(*args)
            except Exception as e:
                if self._pool_donated:
                    e._kv_pool_unsafe = True
                raise
        self._stepstats_launch("cow", _t0)
        self.pool.rebind(k, v)
        self.metrics.cow_copies += 1

    def _ensure_capacity(self):
        """Every running request needs a block for the KV slot its next
        decode step writes; steal from the youngest on exhaustion."""
        bm = self.block_manager
        for req in sorted(
            (r for r in self.slots if r is not None),
            key=lambda r: r.admit_seq,
        ):
            if req.state is not RequestState.RUNNING:
                continue  # preempted by an older request this pass
            need = bm.blocks_needed(req.num_cached + 1)
            while len(req.block_ids) < need:
                if bm.can_allocate(1):
                    req.block_ids += bm.allocate(1)
                    continue
                if (self.prefix_cache is not None
                        and self.prefix_cache.reclaim(1)):
                    continue  # cached block freed: retry the allocate
                if self._reclaim_spec_headroom(1):
                    continue  # idle draft headroom freed: retry
                victims = [
                    r for r in self.slots
                    if r is not None and r is not req
                ]
                if not victims:
                    raise RuntimeError(
                        "KV pool exhausted by a single request; "
                        "EngineConfig.num_blocks is too small for "
                        "max_model_len"
                    )
                self._preempt(max(victims, key=lambda r: r.admit_seq))
        if self._speculating:
            # opportunistic draft headroom: a greedy slot's verify
            # launch writes up to K positions past the required one,
            # so grab blocks for them while the pool has slack — but
            # NEVER preempt or reclaim for it (the host clamps each
            # slot's draft length to its owned-block slack instead, so
            # speculation degrades to plain decode under pressure
            # rather than adding to it)
            cfg = self.config
            k = cfg.speculate_tokens
            for req in self.slots:
                if (req is None or req.state is not RequestState.RUNNING
                        or req.sampling_params.do_sample):
                    continue
                # a request that can only consume w more drafts before
                # its stop condition must not hold headroom beyond
                # them; clamped at the block-table width too — near the
                # length cap the window is cut by _draft_budget instead
                want = min(
                    k,
                    req.sampling_params.max_new_tokens
                    - len(req.output_token_ids) - 1,
                )
                if want <= 0:
                    continue
                need = min(
                    bm.blocks_needed(req.num_cached + 1 + want),
                    cfg.pages_per_seq,
                )
                while len(req.block_ids) < need and bm.can_allocate(1):
                    req.block_ids += bm.allocate(1)

    def _preempt(self, req):
        # restore-instead-of-recompute: snapshot the victim's cached
        # blocks into the host tier BEFORE _release frees them; a
        # successful spill makes the re-admission a host->device
        # restore (no re-prefill) instead of a recompute
        spilled = self._spill_request(req)
        self._release(req)
        req.state = RequestState.WAITING
        req.num_cached = 0
        # the re-prefill this forces recomputes tokens the ledger
        # already counted — classify that waste as preemption
        req.resume_cause = "preempt"
        self.waiting.appendleft(req)
        self.metrics.preemptions += 1
        req.timeline.preemptions += 1
        _flight.record(
            "serving", "preemption", engine=self.engine_id,
            request_id=req.request_id, spilled=spilled,
        )

    # -- host spill tier (serving/spill.py) ----------------------------------
    def _spill_request(self, req):
        """Park ``req``'s cached KV blocks in the host tier as ONE
        handle (all-or-nothing), keyed on the Request so re-admission
        — here, or on a same-host survivor after ``release()`` — can
        restore them. Best effort: any failure (tier disabled, nothing
        cached, injected ``kv.spill`` fault, host budget) returns
        False and the old free-and-recompute path applies unchanged.
        A successful spill is re-ADMITted to the journal so the handle
        key rides next to the emit cursor — a crash replay re-anchors
        it against the disk tier."""
        if self.spill is None or req.num_cached < 1:
            return False
        bm = self.block_manager
        need = bm.blocks_needed(req.num_cached)
        if need > len(req.block_ids):
            return False
        try:
            snaps = [
                self.pool.read_block(b) for b in req.block_ids[:need]
            ]
        except Exception as e:
            # analysis: allow(broad-except) spill is an optimization:
            # an unreadable pool (donation race, backend error) must
            # degrade to plain recompute preemption, never crash
            self.spill.note_spill_failure("request")
            if not self._spill_warned:
                self._spill_warned = True
                warnings.warn(
                    f"[serving] KV spill read failed "
                    f"({type(e).__name__}: {e}); preemption degrades "
                    "to recompute (warned once, counted)",
                    stacklevel=2,
                )
            return False
        key = f"req:{req.request_id}:{self._spill_seq}"
        self._spill_seq += 1
        if not self.spill.put(
            key, snaps, self._spill_signature,
            num_tokens=req.num_cached, cls="request",
        ):
            return False
        req.spill_key = key
        req.spill_tokens = req.num_cached
        if self.journal is not None and not self._journal_replaying:
            # latest-ADMIT-wins: this re-ADMIT carries both the emit
            # cursor and the spill handle (journal "kv" field)
            self.journal.admit(req)
        return True

    def _spill_restorable(self, req, tokens):
        """Admission peek: the token count a spilled handle would
        restore for ``req``, or None for the normal allocate+prefill
        path. Validates the handle against the live tiers (host, disk,
        same-process peers) and this engine's program family — a
        PARTIAL restore leaves a suffix to prefill, which needs the
        prefill_ext program."""
        if self.spill is None or getattr(req, "spill_key", None) is None:
            return None
        n = int(getattr(req, "spill_tokens", 0) or 0)
        if (n < 1 or n > len(tokens)
                or (n < len(tokens) and not self._use_ext)
                or (n >= len(tokens) and not req.output_token_ids)):
            req.spill_key = None
            return None
        if not self.spill.has(req.spill_key, self._spill_signature):
            # the tier LRU-dropped it (or a cross-host migration):
            # recompute path, and stop re-peeking every step
            req.spill_key = None
            return None
        return n

    def _spill_restore(self, req, n_tokens):
        """Write ``req``'s spilled handle back into its freshly
        allocated blocks. True = restored (``num_cached`` may be set
        to ``n_tokens``); False degrades to recompute — the blocks
        stay allocated and the normal prefill rebuilds them. Runs
        under the OOM guard: a RESOURCE_EXHAUSTED device write
        reclaims cold prefix blocks (spilling them colder, to host)
        and retries once before degrading."""
        from .spill import is_resource_exhausted

        t0 = time.perf_counter()
        key, req.spill_key, req.spill_tokens = req.spill_key, None, 0
        payload = self.spill.get(
            key, self._spill_signature, pop=True
        )
        need = self.block_manager.blocks_needed(n_tokens)
        if payload is None or len(payload) < need:
            return False
        for i, (block, snap) in enumerate(
            zip(req.block_ids[:need], payload)
        ):
            try:
                self.pool.write_block(block, snap)
            except Exception as e:
                # analysis: allow(broad-except) the memory-pressure
                # degradation ladder: reclaim -> spill colder blocks
                # -> recompute; admission never unwinds the step
                if is_resource_exhausted(e) and self.prefix_cache \
                        is not None:
                    self.prefix_cache.reclaim(
                        need - i, protect=req.block_ids
                    )
                    try:
                        self.pool.write_block(block, snap)
                        continue
                    except Exception:
                        # analysis: allow(broad-except) same ladder:
                        # the retry exhausts it; recompute below
                        pass
                self.spill.note_restore_failure("request")
                if not self._spill_warned:
                    self._spill_warned = True
                    warnings.warn(
                        f"[serving] KV restore failed "
                        f"({type(e).__name__}: {e}); degrading to "
                        "recompute (warned once, counted)",
                        stacklevel=2,
                    )
                return False
        # goodput attribution: any residual prefill (partial handle)
        # is real forward progress, not preemption waste
        req.resume_cause = "restored"
        self.spill.note_restored(
            "request", payload, time.perf_counter() - t0
        )
        return True

    def _decode(self, finished):
        # one key per scheduler step, shared by isolation re-launches:
        # greedy rows never consume it, and sampled rows see the same
        # uniforms whether or not a poison request was carved out.
        # Drawn unconditionally (even when only the keyless verify
        # program runs) so the key stream advances once per step
        # regardless of the greedy/sampled split.
        key = self._next_key()
        idxs = [
            i for i, r in enumerate(self.slots)
            if r is not None and r.state is RequestState.RUNNING
        ]
        if not self._speculating:
            self._decode_subset(idxs, key, finished)
            return
        # speculation splits the batch by sampling mode: greedy slots
        # go through the verify program (several tokens per launch),
        # sampled slots keep the plain decode path — speculative
        # acceptance is defined against the greedy argmax, and a
        # sampled row's token depends on the warp + key stream, which
        # the verify program deliberately does not carry
        greedy = [
            i for i in idxs
            if not self.slots[i].sampling_params.do_sample
        ]
        sampled = [
            i for i in idxs if self.slots[i].sampling_params.do_sample
        ]
        # drafts are proposed up front: a step where nothing was
        # drafted (no repetition to exploit anywhere) runs the plain
        # single-launch decode over the whole running set instead —
        # bit-identical, and the decode program is cheaper than a
        # draft-less K+1 verify window, so speculation can never be a
        # strict slowdown on non-repetitive traffic
        drafts = {
            i: speculation.propose(
                self._draft_history(self.slots[i]),
                self._draft_budget(self.slots[i]),
                max_ngram=self.config.speculate_ngram,
            )
            for i in greedy
        }
        if not any(drafts.values()):
            self._decode_subset(idxs, key, finished)
            return
        self._verify_subset(greedy, finished, drafts)
        self._decode_subset(sampled, key, finished)

    def _launch_decode(self, idxs, key):
        """Run the compiled decode step with only ``idxs`` active.
        Per-slot outputs are independent (each slot attends to its own
        pages), so any active-mask subset yields the same tokens for its
        members as the full batch would — the property the poison-
        isolation bisection in _decode_subset relies on."""
        self._pin_adapter()
        cfg = self.config
        n = cfg.max_batch_slots
        tokens = np.zeros(n, np.int32)
        positions = np.zeros(n, np.int32)
        tables = np.zeros((n, cfg.pages_per_seq), np.int32)
        active = np.zeros(n, bool)
        for i in idxs:
            req = self.slots[i]
            tokens[i] = req.last_token
            positions[i] = req.num_cached
            tables[i, : len(req.block_ids)] = req.block_ids
            active[i] = True
        params = pack_sampling_params(self.slots)
        faults.fire(
            "serving.step", phase="decode",
            request_ids=tuple(self.slots[i].request_id for i in idxs),
        )
        any_sample = bool(params["do_sample"].any())
        _t0 = time.perf_counter()
        with span(
            "serving.decode", active=len(idxs),
        ), self._watch("serving.decode"), jit_events.watch(
            "serving.decode", kind="serving",
            signature=f"{self.engine_id}:any_sample={any_sample}",
        ):
            try:
                args = (
                    self._launch_weights(), self.pool.k, self.pool.v,
                    tokens, positions, tables, active,
                    params["temperature"], params["top_k"],
                    params["top_p"], params["do_sample"], key,
                )
                if self._cc is not None:
                    # compile-cache mode: AOT executable per static
                    # variant (greedy / mixed-sampling); a variant first
                    # seen mid-serving compiles once, is persisted, and
                    # joins the manifest for the next warm restart
                    exe = self._ensure_program(
                        "decode", any_sample=any_sample
                    )
                    nxt, k, v = exe(*args)
                else:
                    nxt, k, v = self._decode_jit(*args, any_sample)
            except Exception as e:
                # a failure from the dispatched program may have
                # consumed the DONATED pool buffers — re-launching over
                # them would cascade garbage; mark it so isolation
                # re-raises instead (host-side failures before dispatch,
                # e.g. injected faults above, stay containable)
                if self._pool_donated:
                    e._kv_pool_unsafe = True
                raise
            nxt = np.asarray(nxt)
        self._stepstats_launch("decode", _t0)
        self.pool.rebind(k, v)
        self.metrics.decode_steps += 1
        return nxt

    def _isolate(self, idxs, finished, launch, recurse):
        """Shared poison-isolation protocol for batched launches
        (decode and verify): run ``launch(idxs)``; on failure, carve
        the poison request out — by exception attribution
        (``exc.request_id``) or active-mask bisection via
        ``recurse(subset)`` — and finish it with an error while the
        rest still run this step. Returns the launch result, or None
        when containment consumed the failure. Cluster-level aborts
        (CommTimeoutError) and donated-pool losses re-raise: they are
        not containable."""
        try:
            return launch(idxs)
        except CommTimeoutError:
            raise  # cluster-level abort, not a poison request
        except Exception as e:
            if getattr(e, "_kv_pool_unsafe", False):
                raise  # donated pool may be gone: containment impossible
            rid = getattr(e, "request_id", None)
            hit = [
                i for i in idxs if self.slots[i].request_id == rid
            ] if rid is not None else []
            if hit:
                # attributed failure: finish the culprit, run the rest
                self._poison(self.slots[hit[0]], e, finished)
                recurse([i for i in idxs if i != hit[0]])
            elif len(idxs) == 1:
                self._poison(self.slots[idxs[0]], e, finished)
            else:
                mid = len(idxs) // 2
                recurse(idxs[:mid])
                recurse(idxs[mid:])
            return None

    def _decode_subset(self, idxs, key, finished):
        """Decode ``idxs`` with poison isolation (see ``_isolate``)."""
        if not idxs:
            return
        nxt = self._isolate(
            idxs, finished,
            lambda s: self._launch_decode(s, key),
            lambda s: self._decode_subset(s, key, finished),
        )
        if nxt is None:
            return
        cfg, st = self.config, self.stepstats
        for i in idxs:
            req = self.slots[i]
            req.num_cached += 1
            tok = int(nxt[i])
            req.output_token_ids.append(tok)
            req.last_token = tok
            self.metrics.decode_tokens += 1
            req.timeline.decode_tokens += 1
            if st is not None:
                st.note_decode(1)
            reason = req.check_stop(cfg.max_model_len)
            if reason:
                self._finish(req, reason, finished)

    def _reclaim_spec_headroom(self, need):
        """Free up to ``need`` speculative draft-headroom blocks back
        to the pool — tail blocks beyond a greedy RUNNING slot's
        required ``num_cached + 1`` coverage. They hold at most dead
        draft writes (never published, never shared), so freeing them
        is always safe; the slot's next draft budget just shrinks.
        This is what keeps the headroom grab genuinely opportunistic:
        admission and mandatory block growth take it back BEFORE
        shedding, preempting, or refusing a request. Returns the
        number freed."""
        if not self._speculating:
            return 0
        bm = self.block_manager
        freed = 0
        for req in self.slots:
            if freed >= need:
                break
            extra = self._spec_headroom(req)
            while extra > 0 and freed < need:
                bm.free([req.block_ids.pop()])
                extra -= 1
                freed += 1
        return freed

    def _draft_history(self, req):
        """The drafter's bounded history window (prompt + output
        tail), assembled without copying the whole token history every
        step — the per-step host cost must not grow with context
        length."""
        lb = speculation.DEFAULT_LOOKBACK
        out = req.output_token_ids
        if len(out) >= lb:
            return out[-lb:]
        return req.prompt_token_ids[-(lb - len(out)):] + out

    def _draft_budget(self, req):
        """How many draft tokens slot state allows this step: writes
        must stay inside the request's OWNED blocks (headroom is
        opportunistic — see _ensure_capacity) and inside the model
        length, and the request can consume at most remaining-1 drafts
        before a stop condition ends it (proposals past that are
        guaranteed waste). 0 degrades the slot to plain-decode-
        through-verify."""
        cfg = self.config
        ceiling = min(
            len(req.block_ids) * cfg.page_size, cfg.max_model_len
        )
        remaining = (
            req.sampling_params.max_new_tokens
            - len(req.output_token_ids)
        )
        return max(min(cfg.speculate_tokens,
                       ceiling - (req.num_cached + 1),
                       remaining - 1), 0)

    def _launch_verify(self, idxs, drafts):
        """Run the compiled verify step with only ``idxs`` active:
        score each slot's K+1 window (pending token + its entry in
        ``drafts``, proposed once per step in :meth:`_decode`) in one
        launch, return ``(tokens, draft_lens, targets)`` for the
        host-side accept loop. Per-slot outputs are independent (same
        property as _launch_decode), so the poison-isolation bisection
        applies unchanged — re-launches reuse the same drafts."""
        self._pin_adapter()
        cfg = self.config
        n, k = cfg.max_batch_slots, cfg.speculate_tokens
        tokens = np.zeros((n, k + 1), np.int32)
        positions = np.zeros(n, np.int32)
        draft_lens = np.zeros(n, np.int32)
        tables = np.zeros((n, cfg.pages_per_seq), np.int32)
        active = np.zeros(n, bool)
        for i in idxs:
            req = self.slots[i]
            tokens[i, 0] = req.last_token
            positions[i] = req.num_cached
            tables[i, : len(req.block_ids)] = req.block_ids
            active[i] = True
            draft = drafts.get(i, [])
            draft_lens[i] = len(draft)
            tokens[i, 1: 1 + len(draft)] = draft
        faults.fire(
            "serving.step", phase="verify",
            request_ids=tuple(self.slots[i].request_id for i in idxs),
        )
        _t0 = time.perf_counter()
        with span(
            "serving.verify", active=len(idxs),
            proposed=int(draft_lens.sum()),
        ), self._watch("serving.verify"), jit_events.watch(
            "serving.verify", kind="serving",
            signature=f"{self.engine_id}:k={k}",
        ):
            try:
                args = (
                    self._launch_weights(), self.pool.k, self.pool.v,
                    tokens, positions, draft_lens, tables, active,
                )
                if self._cc is not None:
                    exe = self._ensure_program("verify")
                    tgt, kp, vp = exe(*args)
                else:
                    tgt, kp, vp = self._verify_jit(*args)
            except Exception as e:
                # same donated-buffer hazard as decode (_launch_decode)
                if self._pool_donated:
                    e._kv_pool_unsafe = True
                raise
            tgt = np.asarray(tgt)
        self._stepstats_launch("verify", _t0)
        self.pool.rebind(kp, vp)
        self.metrics.verify_steps += 1
        return tokens, draft_lens, tgt

    def _verify_subset(self, idxs, finished, drafts):
        """Speculative decode for greedy slots ``idxs`` with the same
        poison isolation as _decode_subset (see ``_isolate``). On
        success each slot accepts the longest draft prefix matching
        the target argmax and emits accepted+1 tokens — every appended
        token is exactly what a plain decode step would have produced,
        checked through the same per-token stop conditions."""
        if not idxs:
            return
        res = self._isolate(
            idxs, finished,
            lambda s: self._launch_verify(s, drafts),
            lambda s: self._verify_subset(s, finished, drafts),
        )
        if res is None:
            return
        tokens, draft_lens, tgt = res
        cfg, m = self.config, self.metrics
        st = self.stepstats
        for i in idxs:
            req = self.slots[i]
            dlen = int(draft_lens[i])
            a = speculation.accept_length(
                tokens[i, 1: 1 + dlen], tgt[i, :dlen]
            )
            req.timeline.verify_steps += 1
            if dlen:
                # zero-draft slots (nothing to look up, no block
                # slack) are plain decodes, not speculation samples
                m.spec_proposed += dlen
                m.spec_accepted += a
                m.record_spec_accept(a)
                req.timeline.spec_accepted += a
                if st is not None and dlen > a:
                    # rejected drafts consumed verify compute for
                    # tokens nobody keeps — the goodput ledger's
                    # spec-reject class (== proposed - accepted)
                    st.note_spec_reject(dlen - a)
            # emit targets 0..a: the accepted drafts' successors plus
            # the bonus token the rejected/terminal position scored.
            # Their K/V is already in the pages (draft j == target j-1
            # for accepted j); rejected positions' writes are dead —
            # num_cached stops short of them, every later causal mask
            # ends at its own query position, and the next write at
            # that position overwrites.
            for j in range(a + 1):
                tok = int(tgt[i, j])
                req.num_cached += 1
                req.output_token_ids.append(tok)
                req.last_token = tok
                m.decode_tokens += 1
                req.timeline.decode_tokens += 1
                if st is not None:
                    st.note_decode(1)
                reason = req.check_stop(cfg.max_model_len)
                if reason:
                    # stop inside the window (EOS mid-draft, length):
                    # later accepted tokens are discarded unemitted,
                    # exactly where the plain path would have stopped
                    self._finish(req, reason, finished)
                    break

    # -- teardown ------------------------------------------------------------
    def _release(self, req):
        """Free the request's KV blocks and vacate its slot."""
        if req.block_ids:
            self.block_manager.free(req.block_ids)
            req.block_ids = []
        if req.slot is not None:
            self.slots[req.slot] = None
            req.slot = None

    def _finish(self, req, reason, finished):
        if self.spill is not None and getattr(req, "spill_key", None):
            # a parked handle for a request that will never resume is
            # dead budget: release it now instead of waiting for LRU
            self.spill.discard(req.spill_key)
            req.spill_key = None
            req.spill_tokens = 0
        if reason == "aborted" and self.stepstats is not None:
            # the client walked away from every token this request
            # emitted: reclassify them useful -> wasted in the ledger
            self.stepstats.note_abort(len(req.output_token_ids))
        if reason in ("timeout", "error"):
            # degradation events belong in the postmortem ring; normal
            # completions (length/eos/stop) would only drown them out
            _flight.record(
                "serving", reason, engine=self.engine_id,
                request_id=req.request_id, error=req.error,
            )
        req.finish_reason = reason
        req.state = RequestState.FINISHED
        req.finish_time = time.perf_counter()
        # timeline finalization: close the phase record, then the
        # shared finish accounting (access_log.record_finish) — e2e/
        # tpot digests + SLO window (client aborts excluded: not
        # latency samples), access-log line + flight timeline ring
        # (aborts included). All host-side, once per REQUEST.
        req.timeline.mark_finish(reason, req.finish_time)
        record_finish(
            req, latency=self.metrics.latency, slo=self.slo,
            access_log=self.access_log, engine=self.engine_id,
        )
        self._release(req)
        self.metrics.requests_finished += 1
        if self.journal is not None:
            # trailing tokens + terminal record, buffered; the step's
            # group flush (or the next one, for between-step aborts)
            # makes the completion durable
            self.journal.finish(req, reason)
        finished.append(RequestOutput(req))
